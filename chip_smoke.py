#!/usr/bin/env python3
"""Drive the scheduler's device path once on a TPU and check its answers.

    python chip_smoke.py              # one chip: kernels, replay, served,
                                      # tenants
    python chip_smoke.py --chips 4    # four chips: sharded pool only

Phases on one chip, all but the first through the entry points users
call:

- kernels: both Pallas kernels at the edges of their shape domains
  (`repro.kernels.ops`) against the jnp reference.
- replay:  `repro.api.run` of the FB-like trace (526 coflows x 150
  ports, load 0.9) on the jax engine with default kernel dispatch, so
  the Pallas contention kernel runs. Every coflow finishes and the
  average CCT is within 1% of the numpy reference on the same trace.
- served:  the same trace through `CoflowServer` as one long-lived
  tenant, each coflow submitted at its arrival. Per-coflow CCTs equal
  the replay's bitwise (the incremental-replay contract).
- tenants: 256 tenants of `tiny_trace(16, 24, load=0.5)` in one
  `CoflowServer` on a 4:1 leaf-spine with the max-min fill, so both
  Pallas kernels run. CCTs match the same run with `kernel="ref"` within
  the engine's kernel-parity bound, and a sample of tenants matches the
  numpy reference within the cross-engine bound.

With ``--chips 4`` the script runs only the sharded phase: 1024 such
tenants on `SessionPool(shards=4)` against a 1-shard pool, per-tenant
completions bitwise equal, with the mesh and the slab checked to span
the four chips.

Earlier lines give versions, the device, the path each kernel op took
in each phase, compile and run seconds, and every check beside its
limit. The last line is one JSON object; it is printed only when every
phase passed. Without a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0


def _fail(msg: str, code: int = 2):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


class Checks:
    """Every check is printed beside its limit; any failure fails the
    run (after the remaining phases have reported)."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, phase: str, name: str, ok: bool, value,
                 limit) -> None:
        print(f"[{phase}] check {name}: {value} (limit {limit}) "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            self.failed.append(f"{phase}/{name}")


@contextlib.contextmanager
def phase(name: str):
    """Time a phase, splitting jax's compile time (trace, lowering and
    backend compile, from jax.monitoring) from the rest, and count
    which path each kernel op took into the yielded Counter of
    (op, shape, path). jax's in-memory caches are cleared first so every
    op of the phase is traced, and so logged, afresh."""
    import jax

    from repro.kernels import ops

    jax.clear_caches()
    compile0, t0 = _COMPILE_S[0], time.perf_counter()
    paths = collections.Counter()
    with ops.record_paths() as log:
        yield paths
    wall = time.perf_counter() - t0
    comp = _COMPILE_S[0] - compile0
    paths.update(log)
    for (op, shape, path), n in sorted(paths.items()):
        print(f"[{name}] kernel {op} {shape}: {path} (traced {n}x)")
    print(f"[{name}] compile_s={comp} run_s={wall - comp} wall_s={wall}",
          flush=True)


def check_path(check: Checks, name: str, paths, op: str,
               want: str = "pallas") -> None:
    got = sorted({p for (o, _, p) in paths if o == op})
    check(name, f"{op}_path", got == [want], got, f"['{want}']")


_COMPILE_S = [0.0]


def _count_compile(name: str, secs: float, **_) -> None:
    if name.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += secs


# ---- phases ---------------------------------------------------------------

def phase_kernels(check: Checks):
    """Both kernels compiled at the edges of their domains
    (repro.kernels.ops), against the reference on random incidence."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    with phase("kernels"):
        for C, P in ((4096, 512), (4096, ops.CONTENTION_MAX_P)):
            a_s = jnp.asarray(rng.random((C, P)) < 4 / P, jnp.float32)
            a_r = jnp.asarray(rng.random((C, P)) < 4 / P, jnp.float32)
            act = jnp.asarray(rng.random(C) < 0.8)
            got = ops.contention(a_s, a_r, act)
            want = ops.contention(a_s, a_r, act, force="ref")
            bad = int((np.asarray(got) != np.asarray(want)).sum())
            check("kernels", f"contention_{C}x{P}_mismatches", bad == 0,
                  bad, "== 0")
        P, F = ops.MAXMIN_MAX_P, ops.MAXMIN_MAX_F
        src = np.zeros((P, F), np.float32)
        dst = np.zeros((P, F), np.float32)
        src[rng.integers(0, P, F), np.arange(F)] = 1
        dst[rng.integers(0, P, F), np.arange(F)] = 1
        live = jnp.asarray(rng.random(F) < 0.85)
        bw = jnp.asarray(rng.uniform(0.5, 2.0, P), jnp.float32)
        args = (jnp.asarray(src), jnp.asarray(dst), live, bw, bw)
        got = np.asarray(ops.maxmin_rates(*args))
        want = np.asarray(ops.maxmin_rates(*args, force="ref"))
        # the rate tolerance of tests/test_topology.py's kernel parity
        err = float(np.max(np.abs(got - want) - 1e-5 * np.abs(want)))
        check("kernels", f"maxmin_{P}x{F}_excess_over_rtol1e-5",
              err <= 1e-6, err, "<= 1e-6")


def phase_replay(check: Checks, trace):
    import numpy as np

    from repro.api import Scenario, run

    with phase("replay") as paths:
        res = run(Scenario(engine="jax", trace=trace))
        cct = res.row_cct()
    ref = run(Scenario(engine="numpy", trace=trace))
    n = len(trace.coflows)
    done = int(np.isfinite(cct).sum())
    check("replay", "coflows_finished", done == n, done, f"== {n}")
    got, want = float(res.avg_cct[0]), float(ref.avg_cct[0])
    rel = abs(got - want) / want
    print(f"[replay] avg_cct jax={got} numpy={want} "
          f"engine_steps={res.steps}")
    check("replay", "avg_cct_rel_err_vs_numpy", rel <= 0.01, rel, "<= 0.01")
    check_path(check, "replay", paths, "contention")
    return cct


def phase_served(check: Checks, trace, offline_cct):
    import numpy as np

    from repro.core.params import SchedulerParams
    from repro.launch.serve import CoflowServer

    name = "tenant"
    with phase("served") as paths:
        srv = CoflowServer(SchedulerParams(), num_ports=trace.num_ports,
                           max_tenants=1)
        srv.register(name)
        sess = srv._tenants[name]
        cid_of, done = {}, []
        for c in sorted(trace.coflows, key=lambda c: (c.arrival, c.cid)):
            srv.advance(max(c.arrival - sess.now, 0.0))
            cid_of[srv.submit(name, [c])[0]] = c.cid
            done += srv.poll(name)
        advances = len(trace.coflows)
        while srv.num_live(name):
            srv.advance(1.0)
            done += srv.poll(name)
            advances += 1
            if advances > 100_000:
                raise RuntimeError("served tenant failed to drain")
        cct = np.full(len(trace.coflows), np.nan)
        for d in done:
            cct[cid_of[d.handle]] = d.cct
    print(f"[served] advances={advances} slab={srv.stats()['slab']}")
    n = len(trace.coflows)
    same = int((cct == offline_cct).sum())
    check("served", "cct_bitwise_equal_to_replay", same == n, same,
          f"== {n}")
    if same != n:
        diff = np.abs(cct - offline_cct) / np.abs(offline_cct)
        print(f"[served] max rel diff vs replay {np.nanmax(diff)}")
    check_path(check, "served", paths, "contention")


# Virtual seconds per fleet advance. Completions do not depend on it
# (the incremental-replay contract). Each advance's device loop steps
# every lane until the busiest one reaches the horizon, so fewer,
# longer advances run fewer loop iterations (1024 tenants: 3028 at a
# 1 s step, 1073 at 64 s).
FLEET_STEP = 64.0


def _tenant_setup(n: int):
    """`launch.serve.main`'s tenants: parameters and seeded streams."""
    from repro.core.params import SchedulerParams
    from repro.traces.synth import tiny_trace

    params = SchedulerParams(port_bw=1e9, delta=1e-3, start_threshold=1e6)
    traces = [tiny_trace(16, 24, seed=SEED + i, load=0.5) for i in range(n)]
    return params, traces


def _serve_tenants(params, topo, traces, kernel):
    """Every tenant's stream submitted at registration (arrivals in the
    future are held until due); the fleet advances until all drain,
    then each tenant polls. Returns (n_tenants, coflows) CCTs in cid
    order."""
    import numpy as np

    from repro.launch.serve import CoflowServer

    srv = CoflowServer(params, num_ports=traces[0].num_ports,
                       max_tenants=len(traces), topology=topo,
                       kernel=kernel)
    cid_of = {}
    for i, tr in enumerate(traces):
        name = f"tenant/{i}"
        srv.register(name)
        cfs = sorted(tr.coflows, key=lambda c: (c.arrival, c.cid))
        for c, h in zip(cfs, srv.submit(name, cfs)):
            cid_of[name, h] = c.cid
    cct = np.full((len(traces), max(len(t.coflows) for t in traces)),
                  np.nan)
    steps = 0
    while any(srv.num_live(t) for t in srv.tenants):
        srv.advance(FLEET_STEP)
        steps += 1
        if steps > 10_000:
            raise RuntimeError("tenant fleet failed to drain")
    for i in range(len(traces)):
        name = f"tenant/{i}"
        for d in srv.poll(name):
            cct[i, cid_of[name, d.handle]] = d.cct
    return cct, steps, srv.stats()["slab"]


def phase_tenants(check: Checks, n: int = 256, sample: int = 4):
    import numpy as np

    from repro.api import Scenario, run
    from repro.fabric.topology import LeafSpine

    params, traces = _tenant_setup(n)
    topo = LeafSpine(hosts_per_leaf=4, oversub=4.0, wc_fill="maxmin")
    with phase("tenants") as paths:
        cct, steps, slab = _serve_tenants(params, topo, traces, None)
    print(f"[tenants] tenants={n} fleet_advances={steps} slab={slab}")
    for op in ("contention", "maxmin"):
        check_path(check, "tenants", paths, op)
    with phase("tenants-ref") as paths:
        cct_ref, _, _ = _serve_tenants(params, topo, traces, "ref")
    for op in ("contention", "maxmin"):
        check_path(check, "tenants-ref", paths, op, "ref")
    real = np.array([[c < len(t.coflows) for c in range(cct.shape[1])]
                     for t in traces])
    fin = int(np.isfinite(cct[real]).sum())
    check("tenants", "coflows_finished", fin == int(real.sum()), fin,
          f"== {int(real.sum())}")
    # the engine-level kernel-parity bound of tests/test_topology.py
    rel = float(np.nanmax(np.abs(cct - cct_ref)
                          / np.maximum(np.abs(cct_ref), 1e-9)))
    check("tenants", "cct_rel_err_pallas_vs_ref", rel < 1e-3, rel, "< 1e-3")
    # the cross-engine bound of tests/test_topology.py
    worst = 0.0
    for i in range(sample):
        ref = run(Scenario(engine="numpy", trace=traces[i], params=params,
                           topology=topo)).row_cct()
        got = cct[i, :len(ref)]
        worst = max(worst, float(np.nanmax(
            np.abs(got - ref) / np.maximum(np.abs(ref), 1e-9))))
    check("tenants", f"cct_rel_err_vs_numpy_{sample}_tenants", worst < 0.01,
          worst, "< 0.01")


def phase_sharded(check: Checks, n: int = 1024, shards: int = 4):
    import jax

    from repro.api import SessionPool

    # the big-switch fabric of `launch.serve.main`: this phase is about
    # the mesh, and the max-min kernel's cost grows with the rows
    params, traces = _tenant_setup(n)
    records = {}
    for s in (1, shards):
        with phase(f"sharded-{s}") as paths:
            pool = SessionPool(params, num_ports=traces[0].num_ports,
                               max_sessions=n, shards=s)
            sessions = [pool.session() for _ in traces]
            for sess, tr in zip(sessions, traces):
                sess.submit(sorted(tr.coflows,
                                   key=lambda c: (c.arrival, c.cid)))
            index = {id(sess): i for i, sess in enumerate(sessions)}
            rec = {i: [] for i in range(n)}
            steps = 0
            while any(sess.num_live for sess in sessions):
                pool.advance(FLEET_STEP)
                steps += 1
                for sess, d in pool.poll():
                    rec[index[id(sess)]].append(
                        (d.handle, d.cct, tuple(d.fct)))
                if steps > 10_000:
                    raise RuntimeError("sharded fleet failed to drain")
        print(f"[sharded-{s}] sessions={n} advances={steps} "
              f"completions={sum(map(len, rec.values()))}")
        check_path(check, f"sharded-{s}", paths, "contention")
        if s > 1:
            mesh_devs = list(pool._mesh.devices.flat)
            want = jax.devices()[:s]
            check(f"sharded-{s}", "mesh_devices",
                  mesh_devs == want
                  and all(d.platform == "tpu" for d in mesh_devs),
                  [f"{d.platform}:{d.id}" for d in mesh_devs],
                  f"{s} distinct tpu devices")
            spans = {len(leaf.sharding.device_set) for leaf in
                     jax.tree_util.tree_leaves((pool._state, pool._tb))}
            check(f"sharded-{s}", "slab_leaf_device_counts", spans == {s},
                  sorted(spans), f"[{s}]")
        records[s] = rec
    same = sum(records[1][i] == records[shards][i] for i in range(n))
    check("sharded", "per_tenant_bitwise_equal", same == n, same, f"== {n}")


# ---- main -----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}: run this script "
              f"from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    if args.chips > 1:
        # the CLIs' own host-device set-up, to show it leaves the chips
        # that `--shards` gets unchanged
        from repro.launch.entry import force_host_devices

        force_host_devices(["--shards", str(args.chips)])

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU: jax found only {devs[0].platform} devices "
              f"({len(devs)}); this smoke runs on the chip only")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"jax sees {len(devs)}")

    import importlib.metadata as md

    import jaxlib

    from repro.launch.entry import enable_compile_cache

    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}")
    print(f"device_kind={devs[0].device_kind} platform={devs[0].platform} "
          f"count={len(devs)}")
    print(f"compilation_cache_dir="
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    if args.chips > 1:
        print(f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')}")

    check = Checks()
    if args.chips == 1:
        from repro.traces.synth import fb_like_trace

        trace = fb_like_trace(526, 150, seed=SEED, load=0.9)
        phase_kernels(check)
        offline = phase_replay(check, trace)
        phase_served(check, trace, offline)
        phase_tenants(check)
    else:
        phase_sharded(check, shards=args.chips)
    if check.failed:
        _fail(f"{len(check.failed)} check(s) failed: {check.failed}", 1)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
