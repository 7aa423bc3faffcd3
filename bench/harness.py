"""One run of one cell: set-up, the measured window, the comparison.

`run_cell` is everything of a run but the look for a chip, so the
tests can drive it on the CPU at a small size with the timed path
broken underneath.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import shutil
import sys
import time
from pathlib import Path

from bench import compare, fleet as fleet_mod, spec

COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compiles",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/core/compile/jaxpr_trace_duration": "traces",
}


class CompileCounter:
    """Counts jax's compilations (backend compiles less persistent-cache
    hits), cache loads and traces, and their seconds, as jax.monitoring
    reports them."""

    def __init__(self):
        self.n = dict.fromkeys(COMPILE_EVENTS.values(), 0)
        self.secs = 0.0

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        return self

    def _event(self, name, **_):
        if name in COMPILE_EVENTS:
            self.n[COMPILE_EVENTS[name]] += 1

    def _dur(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.n[COMPILE_EVENTS[name]] += 1
        if name.startswith("/jax/core/compile/"):
            self.secs += secs

    def snapshot(self) -> dict:
        out = dict(self.n)
        out["compiles"] = out["backend_compiles"] - out["cache_hits"]
        return out


def log(*parts) -> None:
    print(*parts, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, counter: CompileCounter, devices,
             trace_dir: Path | None = None, keep: dict | None = None,
             rounds: int | None = None) -> dict:
    """A whole run; returns the result object and prints the earlier
    lines. `t_start` is when the process started (set-up's origin).
    `keep`, where given, receives what the comparison was made from
    (the window's completions among it);
    `rounds`, where given, ends the window after that many rounds
    instead of after `seconds` (the tests' fixed amount of work)."""
    import jax

    from repro.kernels import ops

    cfg, traffic = cell.config, cell.traffic
    steps = [float(x) for x in cfg["fast_forward_s"]]
    with ops.record_paths() as paths:
        # the pool's rows are sharded over the cell's own chips
        fl = fleet_mod.Fleet(cfg, traffic, seed, shards=len(devices))
        t0 = time.perf_counter()
        fl.fast_forward(steps)
        t1 = time.perf_counter()
        warm = warm_up(fl, int(cfg["warm_rounds"]))
        t2 = time.perf_counter()
    log(f"setup: virtual {fl.now:.3f}s, live coflows {fl.live()}, "
        f"slab {fl.slab()}, tenants {fl.n}; fast-forward {t1 - t0:.3f}s, "
        f"{warm} warm rounds {t2 - t1:.3f}s, compile {counter.secs:.3f}s "
        f"({counter.snapshot()})")
    for (op, shape, path), n in sorted(collections.Counter(paths).items()):
        log(f"kernel {op} {shape}: {path} (traced {n}x)")
    kernel_shapes = {}
    for op, shape, _ in paths:
        kernel_shapes[op] = shape          # the last traced is current

    gc.collect()
    io0, cc0 = fl.io(), counter.snapshot()
    t_w0_virtual = fl.now
    lat, failed = [], 0
    prof = None
    if trace:
        prof = _start_trace(trace_dir)
        fl.annotate = fleet_mod._annotate
    setup_s = time.perf_counter() - t_start
    w0 = time.perf_counter()
    deadline = w0 + seconds
    n_traced = int(cfg["trace_rounds"])
    io_traced = None
    while True:
        if prof is not None and len(lat) == n_traced:
            # the traced part is over: a trace of every op of the tick's
            # serial loops grows by tens of MB a second. Writing it out
            # takes seconds, which the window gets back, so that a traced
            # run completes as many rounds (and coflows) as another
            s0 = time.perf_counter()
            jax.profiler.stop_trace()
            prof, fl.annotate = None, contextlib.nullcontext
            io_traced = fl.io()
            deadline += time.perf_counter() - s0
        try:
            with fl.annotate("bench.round"):
                lat.append(fl.round("window"))
        except Exception as e:           # a round that raised is a failure
            failed += 1
            log(f"round failed: {type(e).__name__}: {e}")
            fl.now += fl.delta
            if failed > 10:
                break
        if (len(lat) >= rounds if rounds else
                time.perf_counter() >= deadline):
            break
    w1 = time.perf_counter()
    if prof is not None:
        jax.profiler.stop_trace()
        n_traced, io_traced = len(lat), fl.io()
    window_s = w1 - w0
    io1, cc1 = fl.io(), counter.snapshot()
    rounds = len(lat)
    in_window = {k: cc1[k] - cc0[k] for k in cc1}
    log(f"window: {rounds} rounds in {window_s:.6f}s, virtual "
        f"{t_w0_virtual:.3f}..{fl.now:.3f}s, live coflows {fl.live()}, "
        f"slab {fl.slab()}")
    log(f"compiles in window: {in_window['compiles']} "
        f"(backend compiles {in_window['backend_compiles']}, cache loads "
        f"{in_window['cache_hits']}, traces {in_window['traces']})")
    log("pool io over window: " + ", ".join(
        f"{k}={io1[k] - io0[k]}" for k in sorted(io1)))
    log(f"offered load (realized, to {fl.now:.3f}s): "
        f"{fleet_mod.offered_load(fl, fl.now)}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    metrics = {}
    if not trace:
        vals = {
            "realtime_x": rounds * fl.delta * fl.n / window_s,
            "round_ms_p95": fleet_mod.percentile(lat, 95) * 1e3,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
        log(f"round_ms p50={fleet_mod.percentile(lat, 50) * 1e3} "
            f"p95={vals['round_ms_p95']} max={max(lat) * 1e3} "
            f"n={rounds}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        from bench import trace as trace_mod

        ctx = trace_mod.Context.load(
            trace_dir, rounds=n_traced, io=(io0, io_traced),
            kernel_shapes=kernel_shapes, lanes=fl.n,
            device_kind=devices[0].device_kind, n_devices=len(devices))
        for m in cell.per_layer:
            v = spec.layer_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        breakdown = ctx.breakdown()

    # the comparison, once the window has closed and the program's
    # device state is freed
    served_window = fl.served("window")
    served_any = set(fl.done)
    submitted = dict(enumerate(fl.submitted))
    t_end = fl.now
    n_refused = fl.refused
    del fl
    gc.collect()
    r0 = time.perf_counter()
    ref = compare.reference_ccts(cfg, submitted,
                                 t_end + float(cfg["limits"]["margin_s"]))
    vals = compare.readings(
        cfg, submitted,
        {k: v for k, v in served_window.items() if k[0] in submitted},
        served_any, t_w0_virtual, t_end, ref)
    chk = compare.checks(cfg, vals)
    if keep is not None:
        keep.update(submitted=submitted, t0=t_w0_virtual, t1=t_end,
                    served=served_window, readings=vals)
    log(f"reference: {len(submitted)} tenants, "
        f"{time.perf_counter() - r0:.3f}s; "
        f"avg_cct_rel_err {vals['avg_cct_rel_err']}")
    ok = compare.passed(chk) and failed == 0
    out = {"correct": bool(ok), "attempted": rounds + failed,
           "failed": failed + n_refused, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = chk
    return out


def warm_up(fl, least: int, extra: int = 200) -> int:
    """Run set-up rounds: at least `least`, and on (for at most `extra`
    more) until a row re-pack (a submit) and a row gather (a completion)
    have both run since the slab's last full upload. A capacity growth
    late in the fast-forward would otherwise leave the grown slab's row
    scatter and gather to compile inside the window. Returns the rounds
    run."""
    base = None
    n = 0
    while n < least + extra:
        io = fl.io()
        if base is None or io["full_uploads"] != base["full_uploads"]:
            base = io
        elif (n >= least and io["row_uploads"] > base["row_uploads"]
              and io["row_downloads"] > base["row_downloads"]):
            break
        fl.round("setup")
        n += 1
    return n


def _start_trace(trace_dir: Path):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    return trace_dir


def print_checks(chk: dict) -> None:
    for k, v in chk.items():
        rel = ">=" if k == "window_coflows" else "<="
        print(f"check {k}: {v['value']} (limit {rel} {v['limit']})",
              file=sys.stderr, flush=True)
