"""Sharded mega-pool (ISSUE 6): the row axis partitioned across
devices via the pmap dispatch path.

The load-bearing property is BITWISE parity: an N-shard pool produces
bit-identical per-session CCTs/FCTs to the 1-shard (single-device)
pool, async and blocking dispatch alike — pmap runs the exact
single-slab program per device (no GSPMD partitioner, no collectives),
so sharding is purely a placement decision. CPU runners get the
devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the CI sharded step / `make pool-sharded`); sharded cases skip when
the devices aren't there, the async-vs-blocking case runs everywhere.
"""
import numpy as np
import pytest

import jax

from repro.api import SessionPool
from repro.core.coflow import Coflow, Flow
from repro.core.params import SchedulerParams

PORTS = 6
PARAMS = SchedulerParams(port_bw=1.0, delta=1e-2, start_threshold=4.0,
                         growth=4.0, num_queues=5)

needs_devices = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _coflows(seed: int, n: int, spread: float = 2.0):
    rng = np.random.default_rng(seed)
    cfs, fid = [], 0
    for c in range(n):
        w = int(rng.integers(1, 5))
        flows = [Flow(fid + i, int(rng.integers(0, PORTS)),
                      int(rng.integers(0, PORTS)),
                      float(rng.uniform(1.0, 15.0))) for i in range(w)]
        fid += w
        cfs.append(Coflow(c, float(rng.uniform(0.0, spread)), flows))
    return sorted(cfs, key=lambda c: (c.arrival, c.cid))


def _run_fleet(shards: int, *, async_dispatch: bool = True, B: int = 8,
               steps: int = 40, dt: float = 0.9, late_join: bool = True,
               io: dict | None = None):
    """An adversarial fleet script: B sessions with different
    workloads, one admitted mid-run onto a recycled row, one released
    early; returns per-session completion records (handle, cct, fcts)
    in a canonical layout for bitwise comparison. `io`, where given,
    receives the pool's `io` counters at the end."""
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=B,
                       shards=shards, async_dispatch=async_dispatch)
    sessions = [pool.session() for _ in range(B)]
    for i, s in enumerate(sessions):
        s.submit(_coflows(100 + i, 3 + i % 3))
    results = {i: [] for i in range(B + 1)}
    extra = None
    for step in range(steps):
        pool.advance(dt)
        if step == 5 and late_join:
            sessions[1].close()           # frees a row mid-run...
            extra = pool.session()        # ...recycled by a late joiner
            extra.submit(_coflows(999, 2, spread=0.5))
        for s, d in pool.poll():
            key = B if s is extra else sessions.index(s)
            results[key].append((d.handle, d.cct, tuple(d.fct)))
    for s in sessions:
        if s._pool is not None:
            s.close()
    if extra is not None:
        extra.close()
    if io is not None:
        io.update(pool.io)
    return results


@needs_devices
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_pool_bitwise_equals_single_device(shards):
    ref = _run_fleet(1)
    got = _run_fleet(shards)
    assert got == ref, (
        f"{shards}-shard pool diverged from the single-device pool")


@needs_devices
@pytest.mark.parametrize("shards", [2, 4])
def test_work_counters_equal_across_shards(shards):
    """Each shard's loop counts its own rows' work: the pool's summed
    counters (and the longest loop's event steps) are the 1-shard
    pool's, async and blocking alike."""
    work = ("event_steps", "lane_steps", "admit_trips", "wc_trips",
            "wc_fills")
    got = {}
    for key in [(1, True), (shards, True), (shards, False)]:
        io: dict = {}
        _run_fleet(key[0], async_dispatch=key[1], io=io)
        got[key] = {k: io[k] for k in work}
    assert got[(shards, True)] == got[(1, True)] == got[(shards, False)]
    assert got[(1, True)]["wc_trips"] > 0
    assert 0 < got[(1, True)]["wc_fills"] <= got[(1, True)]["wc_trips"]


@needs_devices
def test_sharded_blocking_path_bitwise_too():
    """The MAX_REL_TICKS split loop (blocking path) through the pmap
    dispatch is the same arithmetic as the async fast path."""
    assert _run_fleet(4, async_dispatch=False) == \
        _run_fleet(1, async_dispatch=False)


def test_async_dispatch_bitwise_equals_blocking():
    """Async double-buffering is pure pipelining: deferring the ctl
    download can never change a row's arithmetic (runs on any device
    count)."""
    assert _run_fleet(1, async_dispatch=True) == \
        _run_fleet(1, async_dispatch=False)


def test_async_dispatch_defers_ctl_downloads():
    """A burst of K advances costs K dispatches but ONE deferred ctl
    download at the next sync point."""
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=2,
                       async_dispatch=True)
    s = pool.session()
    s.submit(_coflows(7, 3))
    pool.advance(0.2)                      # first dispatch + ensure
    pool.poll()                            # sync: a clean baseline
    d0 = pool.io["dispatches"]
    c0 = pool.io["ctl_bytes"]
    for _ in range(5):
        pool.advance(0.05)                 # chained: no ctl download
    assert pool.io["dispatches"] == d0 + 5
    assert pool.io["ctl_bytes"] == c0
    pool.poll()                            # ONE download for the burst
    burst = pool.io["ctl_bytes"] - c0
    assert burst > 0
    pool.advance(0.05)
    pool.poll()
    single = pool.io["ctl_bytes"] - c0 - burst
    assert burst == single, "K chained advances must cost ONE ctl read"


def test_shard_validation():
    with pytest.raises(ValueError, match="multiple of shards"):
        SessionPool(PARAMS, num_ports=PORTS, max_sessions=6, shards=4)
    if jax.device_count() < 64:
        with pytest.raises(ValueError, match="devices"):
            SessionPool(PARAMS, num_ports=PORTS, max_sessions=64,
                        shards=64)


def test_pinned_features_join_never_recompiles():
    """The pinned-features serving contract, enforced at the XLA cache:
    once the fleet executables are warm, admitting a NEW tenant — even
    one with heterogeneous SchedulerParams — must be pure data movement
    (a row scatter + the warm dispatch), zero fresh compiles. Params
    live in the stacked EngineParams rows, so per-tenant values change
    operands, never the traced program."""
    from repro.analysis.sanitize import assert_no_recompiles

    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=4,
                       features=(True, True, False))
    a = pool.session()
    a.submit(_coflows(11, 3))
    pool.advance(0.5)                      # compile the fleet programs
    b = pool.session()                     # warm the JOIN path too:
    b.submit(_coflows(12, 2))              # k=1 scatter + ep restack
    pool.advance(0.5)
    pool.poll()                            # ...and the gather/sync path
    hetero = SchedulerParams(port_bw=1.0, delta=2e-2,
                             start_threshold=8.0, growth=4.0,
                             num_queues=5)
    with assert_no_recompiles():
        c = pool.session(params=hetero)
        c.submit(_coflows(13, 2, spread=0.5))
        pool.advance(0.5)
    pool.poll()                            # gather idx shape varies —
    pool.advance(5.0)                      # correctness stays outside
    assert {s for s, _ in pool.poll()} <= {a, b, c}


def test_pinned_features_reject_out_of_superset_tenant():
    """Pinned features freeze the compiled structure: a tenant whose
    mechanisms need a feature outside the pinned set is refused at
    admission (instead of silently recompiling the fleet)."""
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=2,
                       features=(True, True, False))
    s = pool.session()                     # defaults fit the pinned set
    s.submit(_coflows(3, 2))
    pool.advance(0.5)
    with pytest.raises(ValueError, match="pinned"):
        pool.session(mechanisms={"lcof": False})  # needs ablations
    # the refusal didn't leak a row
    assert pool.num_sessions == 1
    pool.advance(2.0)
