"""A fleet of leaf-spine max-min tenants on a row-sharded pool, through
the served path (`CoflowServer(..., shards=4)` over `SessionPool`), on
seeded random traffic that grows the shared slab once and scatters many
different numbers of dirty rows: the four-shard pool hands out the
one-shard pool's completions bit for bit, only finite CCTs, and the
float64 reference's CCTs by the median gap the benchmark compares.

The four host devices exist only in a child process
(`XLA_FLAGS=--xla_force_host_platform_device_count=4`, set before jax
starts), so the test runs the fleet there."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_FLEET = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
import numpy as np

from bench import reference
from repro.core.coflow import Coflow, Flow
from repro.core.params import SchedulerParams
from repro.fabric.topology import LeafSpine
from repro.launch.serve import CoflowServer

assert len(jax.devices()) == 4, jax.devices()
PARAMS = dict(delta=1e-2, num_queues=5, start_threshold=4.0, growth=4.0,
              deadline_factor=2.0, port_bw=1.0, min_rate_frac=1e-3)
TOPO = dict(kind="leaf_spine", hosts_per_leaf=4, oversub=4.0,
            wc_fill="maxmin")
TENANTS, PORTS, DT, STEPS = 16, 8, 0.05, 50
# tenants that submit in each step: every count from 1 to all of them
COUNTS = (1, 2, 3, 5, 8, 13, 16, 4, 7, 11)


class Spec:
    def __init__(self, cid, arrival, src, dst, size):
        self.cid, self.arrival = cid, arrival
        self.src, self.dst, self.size = src, dst, size


def traffic():
    rng = np.random.default_rng(20240601)
    steps = []
    for k in range(STEPS):
        who = rng.choice(TENANTS, COUNTS[k % len(COUNTS)], replace=False)
        batch = {{}}
        for i in sorted(who.tolist()):
            specs = []
            # one burst outgrows the slab's 16 coflows a row: a doubling
            n = 24 if (k, i) == (10, int(who[0])) else int(rng.integers(1, 4))
            for j in range(n):
                w = int(rng.integers(1, 7))
                src = rng.integers(0, PORTS, w)
                dst = (src + rng.integers(1, PORTS, w)) % PORTS
                specs.append(Spec(1000 * k + j, (k + rng.random()) * DT,
                                  src, dst, rng.uniform(0.005, 0.05, w)))
            batch[i] = specs
        steps.append(batch)
    return steps


def drive(shards, steps):
    srv = CoflowServer(SchedulerParams(**PARAMS), num_ports=PORTS,
                       max_tenants=TENANTS, shards=shards,
                       topology=LeafSpine(4, 4.0, "maxmin"))
    names = [f"t{{i}}" for i in range(TENANTS)]
    for n in names:
        srv.register(n)
    cid = [{{}} for _ in names]
    served, dirty = {{}}, set()
    for k, batch in enumerate(steps + [{{}}] * 40):
        for i, specs in batch.items():
            hs = srv.submit(names[i], [
                Coflow(s.cid, s.arrival,
                       [Flow(j, int(a), int(b), float(z)) for j, (a, b, z)
                        in enumerate(zip(s.src, s.dst, s.size))])
                for s in specs])
            cid[i].update(zip(hs, (s.cid for s in specs)))
        dirty.add(sum(s._tb_dirty for s in srv.pool.sessions))
        srv.advance(DT)
        for i, n in enumerate(names):
            for d in srv.poll(n):
                served[f"{{i}}/{{cid[i][d.handle]}}"] = d.cct
    return served, dict(srv.pool.io), sorted(dirty), (k + 1) * DT


steps = traffic()
out = {{}}
for shards in (1, 4):
    served, io, dirty, end = drive(shards, steps)
    out[shards] = dict(served={{k: float(v).hex() for k, v in served.items()}},
                       finite=bool(np.isfinite(list(served.values())).all()),
                       io=io, dirty=dirty)
subs = {{i: [s for b in steps for s in b.get(i, [])] for i in range(TENANTS)}}
rel = []
for i, specs in subs.items():
    ref = reference.run(specs, PARAMS, PORTS, until=end, topology=TOPO)
    for s, r in zip(specs, ref):
        got = out[4]["served"].get(f"{{i}}/{{s.cid}}")
        if got is not None:
            rel.append(abs(float.fromhex(got) - r) / r)
out["rel"] = rel
out["coflows"] = sum(len(v) for v in subs.values())
print(json.dumps(out))
"""


def test_four_shards_of_maxmin_pods_serve_what_one_does():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", _FLEET.format(root=str(ROOT),
                                             src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    one, four = out["1"], out["4"]
    # every coflow submitted was served, with a finite CCT, bit for bit
    # what one device serves
    assert len(four["served"]) == out["coflows"]
    assert four["finite"] and one["finite"]
    assert four["served"] == one["served"]
    # the run grew the shared slab and scattered many dirty-row counts
    assert four["io"]["full_uploads"] >= 2
    assert len([n for n in four["dirty"] if n]) >= 5
    assert four["io"]["pad_rows"] > 0
    # the float64 reference finished every coflow the pool served. An
    # f32 schedule and the f64 one fork on near-ties, and a fork moves
    # every later CCT of the pod, so the median gap is compared, as the
    # benchmark compares it (bench/compare.py): most agree to 1e-5
    rel = sorted(out["rel"])
    assert len(rel) == out["coflows"]
    assert rel[len(rel) // 2] < 1e-4
