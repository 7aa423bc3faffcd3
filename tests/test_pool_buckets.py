"""Bucketed row scatters and gathers of the `SessionPool`: a dirty-row
queue is padded to a power of two with pad rows past the last row of
their shard, so the pool runs each bucket once, when the slab is
built (nothing compiles or loads from the cache afterwards), and the
pads leave the slab bit for bit as it was; and the
sharded pool's per-device sync points (scatters, gathers, wave
planning, the debug view) give what one device gives.

The sharded pool needs four host devices, which exist only in a child
process (`XLA_FLAGS` is read when jax starts); the checks run there for
both pools and each is asserted by its own test case."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

_CHECKS = r"""
import json, sys
sys.path[:0] = [{src!r}]
import jax
import numpy as np

from repro.api import SessionPool
from repro.api.pool import _bucket
from repro.core.coflow import Coflow, Flow
from repro.core.params import SchedulerParams
from repro.fabric import jax_engine as je
from repro.fabric.topology import LeafSpine

PARAMS = SchedulerParams(port_bw=1.0, delta=1e-2, start_threshold=4.0,
                         growth=4.0, num_queues=5)
B, PORTS = 16, 8


# backend compiles and persistent-cache loads, as jax.monitoring
# reports them (a cache load also costs a round its time)
EVENTS = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: EVENTS.append(name)
    if name == "/jax/core/compile/backend_compile_duration" else None)
jax.monitoring.register_event_listener(
    lambda name, **kw: EVENTS.append(name)
    if name == "/jax/compilation_cache/cache_hits" else None)


def run(shards):
    rng = np.random.default_rng(5)
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=B,
                       shards=shards, topology=LeafSpine(4, 4.0, "maxmin"),
                       min_coflow_capacity=32, min_flow_capacity=256)
    ss = [pool.session() for _ in range(B)]

    def cf(k):
        w = int(rng.integers(1, 4))
        return Coflow(k, 0.0, [Flow(j, int(rng.integers(PORTS)),
                                    int(rng.integers(PORTS)),
                                    float(rng.uniform(0.01, 0.1)))
                               for j in range(w)])

    for s in ss:
        s.submit([cf(0)])
    pool.advance(0.02)
    pool.poll()
    ss[0].submit([cf(0)])          # one more round: a chained dispatch
    pool.advance(0.02)
    pool.poll()
    # every scatter from here on: the slab before and after, the call,
    # as flat rows and global indices (a sharded pool's are folded and
    # shard-local, pads at the local index B/shards)
    scatters = []
    per = B // shards

    def flat(x, lead):
        x = np.asarray(x)
        return x.reshape(-1, *x.shape[lead:])

    def glob(i):
        i = np.asarray(i).reshape(shards, -1)
        return np.where(i < per, i + per * np.arange(shards)[:, None],
                        B).reshape(-1)

    lead = 1 if shards == 1 else 2
    real = pool._rows

    def spy(tree, idx, rows):
        fused = type(tree) is tuple
        tree, idx, rows = ((tree, idx, rows) if fused
                           else ((tree,), (idx,), (rows,)))
        before = jax.tree_util.tree_map(lambda x: flat(x, lead), tree)
        out = real.scatter(tree if fused else tree[0],
                           idx if fused else idx[0],
                           rows if fused else rows[0])
        after = jax.tree_util.tree_map(lambda x: flat(x, lead),
                                       out if fused else (out,))
        rows = jax.tree_util.tree_map(lambda x: flat(x, lead), rows)
        scatters.append((before, tuple(map(glob, idx)), rows, after))
        return out

    pool._rows = real._replace(scatter=spy)
    n0, pads0 = len(EVENTS), pool.io["pad_rows"]
    counts, state_only = set(), 0
    for k in range(1, 40):
        n = int(rng.integers(1, B + 1))
        counts.add(n)
        if k % 5 == 0:
            # force-completes re-scatter state rows only
            for i in rng.choice(B, n, replace=False):
                ss[i].complete(list(ss[i]._live)[:1])
            state_only += 1
        else:
            for i in rng.choice(B, n, replace=False):
                ss[i].submit([cf(k)])
        pool.advance(0.02)
        pool.poll()
    compiles = EVENTS[n0:]
    # pads at index B change nothing: each real row is its payload, each
    # other row (and each leaf) is bit for bit what it was
    pads = 0
    for before, idx, rows, after in scatters:
        for b, i, r, a in zip(before, idx, rows, after):
            i = np.asarray(i)
            keep = np.ones(B, bool)
            keep[i[i < B]] = False
            for x, u, y in zip(jax.tree_util.tree_leaves(b),
                               jax.tree_util.tree_leaves(r),
                               jax.tree_util.tree_leaves(a)):
                want = x.copy()
                want[i[i < B]] = np.asarray(u)[i < B]
                assert want.tobytes() == y.tobytes()
        pads += int((np.asarray(idx[-1]) >= B).sum())   # state rows
    # what a sync point compiles to: per-device programs that move no
    # row between devices (no collective), on the pool's own slab
    collectives = []
    if shards > 1:
        idx = np.zeros((shards, 4), np.int32)
        rows = jax.tree_util.tree_map(lambda a: a[:, :4], (pool._tb,
                                                           pool._state))
        texts = [real.gather.lower(pool._state, idx).compile().as_text(),
                 real.scatter.lower((pool._tb, pool._state), (idx, idx),
                                    rows).compile().as_text()]
        collectives = [op for op in ("all-reduce", "all-gather",
                                     "all-to-all", "collective-permute")
                       for t in texts if op in t]
    return dict(compiles=compiles, counts=sorted(counts),
                collectives=collectives, state_only=sum(
                    1 for c in scatters if len(c[1]) == 1),
                scatters=len(scatters), pads=pads,
                io_pads=pool.io["pad_rows"] - pads0,
                idx_sizes=sorted({{int(c[1][-1].size) // shards
                                   for c in scatters}}),
                buckets=[_bucket(n) for n in range(1, per + 1)])


def one_tenant():
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=1)
    s = pool.session()
    shapes = []
    s.submit([Coflow(9, 0.0, [Flow(0, 0, 1, 0.05)])])
    pool.advance(0.02)             # builds the slab (and runs its warm)
    pool.poll()
    base = pool.io["scatter_rows"]

    def spy(tree, idx, rows):
        # [fused?, idx shapes, payload rows] of each scatter
        fused = type(tree) is tuple
        ii, rr = (idx, rows) if fused else ((idx,), (rows,))
        shapes.append([fused, [list(np.asarray(i).shape) for i in ii],
                       [jax.tree_util.tree_leaves(r)[0].shape[0]
                        for r in rr]])
        return je.scatter_rows(tree, idx, rows)

    pool._rows = pool._rows._replace(scatter=spy)
    for k in range(6):
        s.submit([Coflow(k, 0.0, [Flow(0, 0, 1 + k % 5, 0.05)])])
        pool.advance(0.02)
        pool.poll()
    return dict(shapes=shapes, io=pool.io,
                scattered=pool.io["scatter_rows"] - base)


def plan_and_view(shards):
    # wave planning and the debug view of a pool: the admitted handles
    # of each planning tick, and a digest of the slab's numpy view
    import hashlib

    rng = np.random.default_rng(9)
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=B,
                       shards=shards)
    ss = [pool.session() for _ in range(B)]
    for i, s in enumerate(ss):
        s.submit([Coflow(k, 0.0, [Flow(0, int(a), int(b), 1.0)])
                  for k, (a, b) in enumerate(
                      rng.integers(0, PORTS, (3 + i % 3, 2)))])
    pool.advance(0.02)
    waves = [ss[i].plan_tick() for i in (0, 5, 9, 5, 15)]
    tb, st = pool.host_view()
    view = hashlib.sha256(b"".join(
        np.ascontiguousarray(x).tobytes()
        for x in jax.tree_util.tree_leaves((tb, st)))).hexdigest()
    return dict(waves=waves, view=view)


print(json.dumps({{"1": run(1), "4": run(4), "one": one_tenant(),
                  "plan": {{n: plan_and_view(n) for n in (1, 4)}}}},
                 default=str))
"""


@pytest.fixture(scope="module")
def checks():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", _CHECKS.format(src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("shards", ["1", "4"])
def test_pad_rows_leave_the_slab_as_it_was(checks, shards):
    out = checks[shards]
    # (the child asserted every leaf of every scatter bit for bit)
    assert out["scatters"] > 0 and out["pads"] > 0
    assert out["io_pads"] == out["pads"]
    assert len(out["counts"]) >= 5


@pytest.mark.parametrize("shards", ["1", "4"])
def test_scatter_compiles_nothing_after_the_build(checks, shards):
    out = checks[shards]
    # many dirty-row counts, each padded to a power of two, fused and
    # state-only scatters ...
    assert set(out["idx_sizes"]) <= set(out["buckets"])
    assert len(out["idx_sizes"]) >= 3
    assert 0 < out["state_only"] < out["scatters"]
    # ... and every bucket ran when the slab was built: the rounds
    # compile nothing and load nothing from the persistent cache
    assert out["compiles"] == []


def test_sharded_sync_points_move_no_row_between_devices(checks):
    assert checks["4"]["collectives"] == []


def test_one_tenant_pool_scatters_one_row(checks):
    out = checks["one"]
    # fused (TraceBatch and state) or state-only, as before buckets:
    # one row at index shape (1,), never a pad
    assert any(f for f, _, _ in out["shapes"])
    assert all(i == [[1]] * (1 + f) and r == [1] * (1 + f)
               for f, i, r in out["shapes"])
    assert out["io"]["pad_rows"] == 0
    assert out["scattered"] == len(out["shapes"])


def test_sharded_plan_tick_and_host_view_match_one_device(checks):
    one, four = checks["plan"]["1"], checks["plan"]["4"]
    assert one["waves"] == four["waves"]
    assert any(one["waves"])
    assert one["view"] == four["view"]


def test_bucket_is_the_next_power_of_two():
    from repro.api.pool import _bucket

    got = [_bucket(n) for n in range(1, 18)]
    assert got == [1, 2, 4, 4, 8, 8, 8, 8, 16, 16, 16, 16, 16, 16, 16,
                   16, 32]
    assert np.all(np.diff(got) >= 0)
