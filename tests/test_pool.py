"""SessionPool: K sessions on one slab == K standalone sessions,
bitwise — plus admission control and the CoflowServer front door.

The acceptance contract (ISSUE 4): a pooled fleet changes the DISPATCH
structure (one vmapped scan instead of K sequential ones), never the
arithmetic. Mid-run admission, capacity doubling triggered by one row,
and a session finishing while others run must all leave every
session's CCTs/FCTs bitwise-equal to the same session run standalone.
"""
import dataclasses

import numpy as np
import pytest

from repro.analysis.sanitize import assert_no_transfers
from repro.api import SaathSession, SessionPool
from repro.core.coflow import Coflow, Flow
from repro.core.params import SchedulerParams

PORTS = 6
PARAMS = SchedulerParams(port_bw=1.0, delta=1e-2, start_threshold=4.0,
                         growth=4.0, num_queues=5)


def _coflows(seed: int, n: int, base: int = 0, spread: float = 2.0):
    rng = np.random.default_rng(seed)
    cfs, fid = [], 0
    for c in range(n):
        w = int(rng.integers(1, 5))
        flows = [Flow(fid + i, int(rng.integers(0, PORTS)),
                      int(rng.integers(0, PORTS)),
                      float(rng.uniform(1.0, 15.0))) for i in range(w)]
        fid += w
        cfs.append(Coflow(base + c, float(rng.uniform(0.0, spread)),
                          flows))
    return cfs


def _harvest(results, sessions):
    for i, s in enumerate(sessions):
        results[i].update({d.handle: (d.cct, tuple(d.fct))
                           for d in s.poll()})


@pytest.mark.parametrize("seed", [0, 1])
def test_pool_bitwise_equals_standalone_sessions(seed):
    """The property test: K pooled sessions vs K standalone ones under
    an adversarial script — session 2 admitted mid-run, session 0
    doubling the shared coflow capacity with a burst, session 1 tiny so
    it finishes while the others still run — produce bitwise-identical
    per-session CCTs and FCTs. The script is advance-cadence-identical
    on both sides (same dt sequence from each session's birth)."""
    workloads = [_coflows(seed, 6), _coflows(seed + 50, 2, spread=0.5),
                 _coflows(seed + 100, 5)]
    burst = _coflows(seed + 200, 20, base=500, spread=1.0)

    def script(make_session, advance_all):
        # phases: [s0, s1] run; s2 admitted after 3 steps; s0 bursts
        # past the 16-row coflow capacity after 5 steps
        sessions = [make_session(), make_session()]
        results = [dict(), dict(), dict()]
        for s, w in zip(sessions, workloads[:2]):
            s.submit(sorted(w, key=lambda c: (c.arrival, c.cid)))
        s1_drained_at = None
        for step in range(200):
            if step == 3:
                s2 = make_session()
                s2.submit(sorted(workloads[2],
                                 key=lambda c: (c.arrival, c.cid)))
                sessions.append(s2)
            if step == 5:
                sessions[0].submit(
                    sorted(burst, key=lambda c: (c.arrival, c.cid)))
            advance_all(sessions, 0.9)
            _harvest(results, sessions)
            if s1_drained_at is None and not sessions[1].num_live:
                s1_drained_at = step
            if not any(s.num_live for s in sessions):
                assert s1_drained_at < step, \
                    "script expects session 1 to finish early"
                return results
        raise RuntimeError("script failed to drain")

    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=4)

    def pool_advance(sessions, dt):
        pool.advance(dt)  # ONE dispatch chain for every row

    pooled = script(pool.session, pool_advance)
    assert pool._C_cap >= 26                     # the burst doubled it

    def standalone_advance(sessions, dt):
        for s in sessions:
            s.advance(dt)

    solo = script(
        lambda: SaathSession(PARAMS, num_ports=PORTS, backend="jax"),
        standalone_advance)
    assert pooled == solo


def test_pool_admission_cap_and_row_recycling():
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=2)
    a, b = pool.session(), pool.session()
    assert pool.num_sessions == 2
    with pytest.raises(RuntimeError, match="full"):
        pool.session()
    a.submit(_coflows(3, 2))
    pool.advance(0.5)
    pool.release(a)                  # frees row 0 (drops a's coflows)
    with pytest.raises(RuntimeError, match="closed"):
        a.advance(0.1)
    c = pool.session()               # recycled row
    assert c._row == 0 and pool.num_sessions == 2
    c.submit(_coflows(4, 2))
    done = []
    for _ in range(100):
        pool.advance(1.0)
        done += c.poll()
        if not c.num_live:
            break
    assert len(done) == 2 and all(np.isfinite(d.cct) for d in done)
    assert b.num_live == 0           # b never submitted; clock moved
    assert b.now > 0


def test_pool_idle_sessions_do_not_block_the_fleet():
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=3)
    idle = pool.session()
    busy = pool.session()
    busy.submit(_coflows(7, 3))
    done = []
    for _ in range(100):
        pool.advance(1.0)
        done += busy.poll()
        if not busy.num_live:
            break
    assert len(done) == 3
    assert idle.num_live == 0 and idle.now == busy.now


def test_single_session_advance_noops_other_rows():
    """`advance` on ONE pooled view moves only its row; the others'
    coordinators stay frozen at their own horizons."""
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=2)
    a, b = pool.session(), pool.session()
    a.submit(_coflows(9, 3))
    b.submit(_coflows(10, 3))
    a.advance(200.0)
    assert a.now == 200.0 and b.now == 0.0
    done_a = a.poll()
    assert len(done_a) == 3          # a drained alone
    assert not b.poll()              # b never ticked
    b.advance(200.0)
    assert len(b.poll()) == 3


def test_pool_device_resident_clean_rows_never_reupload():
    """The ISSUE-5 tentpole contract: after the first (full) upload,
    advances over clean rows move ZERO slab bytes host->device; only
    rows whose membership/state changed are scattered, and host
    mirrors materialize lazily (on poll), not per advance."""
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=3)
    a, b = pool.session(), pool.session()
    # big flows so nothing completes during the probe advances
    a.submit([Coflow(0, 0.0, [Flow(0, 0, 1, 500.0)])])
    b.submit([Coflow(0, 0.0, [Flow(0, 2, 3, 500.0)])])
    pool.advance(1.0)                     # first _ensure: ONE full upload
    io = pool.io
    assert io["full_uploads"] == 1
    base_rows, base_bytes = io["row_uploads"], io["upload_bytes"]
    downloads = io["row_downloads"]
    # guard + counters together make "zero clean-row uploads"
    # structural: an UNACCOUNTED h2d upload raises inside the guard,
    # an accounted one moves the io counters asserted unchanged below
    with assert_no_transfers():
        for _ in range(5):
            pool.advance(1.0)             # clean rows: nothing uploads
    assert io["full_uploads"] == 1
    assert io["row_uploads"] == base_rows
    assert io["upload_bytes"] == base_bytes
    assert io["row_downloads"] == downloads   # nobody looked: no gathers
    a.submit([Coflow(1, a.now, [Flow(1, 1, 2, 500.0)])])  # dirty ONE row
    pool.advance(1.0)
    assert io["full_uploads"] == 1            # still no full mirror
    assert io["row_uploads"] == base_rows + 1  # just a's row scattered
    # nothing completed: polling gathers NOTHING (the completions-only
    # fast path), while a snapshot forces the lazy row materialization
    downloads = io["row_downloads"]
    assert a.poll() == [] and b.poll() == []
    assert io["row_downloads"] == downloads
    assert a.snapshot()[0]["sent"] > 0
    assert io["row_downloads"] > downloads    # ...via row gathers
    tb, st = pool.host_view()                 # the lazy debug view
    assert isinstance(tb.size, np.ndarray)
    assert int(np.asarray(st.tick).max()) > 0


def test_pool_epoch_rebase_is_per_row():
    """Regression (ISSUE 5): the f32 epoch re-base is strictly PER ROW.
    One row ages past REBASE_TICKS and re-bases on its next re-pack
    while its neighbor stays young at epoch 0 — both rows must keep
    full δ resolution (a slab-global re-base would drag the young
    row's times negative and fork its trajectory)."""
    from repro.api.pool import REBASE_TICKS

    t_off = 2.0 * REBASE_TICKS * PARAMS.delta   # 2^21 ticks ~ 21000s
    rng = np.random.default_rng(17)

    def workload(base):
        # binary-exact relative arrivals/sizes (0.25-grained): any
        # mismatch is a lost-resolution f32 slab artifact
        cfs, fid = [], 0
        for c in range(5):
            w = int(rng.integers(1, 4))
            flows = [Flow(fid + i, int(rng.integers(0, PORTS)),
                          int(rng.integers(0, PORTS)),
                          float(rng.integers(4, 60) * 0.25))
                     for i in range(w)]
            fid += w
            cfs.append(Coflow(c, base + 0.25 * int(rng.integers(0, 8)),
                              flows))
        return cfs

    state = rng.bit_generator.state
    base_cfs = workload(0.0)
    rng.bit_generator.state = state              # identical draws
    late_cfs = workload(t_off)

    ref = SaathSession(PARAMS, num_ports=PORTS, backend="jax")
    ref.submit(base_cfs)
    want = {d.handle: (d.cct, tuple(d.fct))
            for d in ref.drain(step=5.0, max_seconds=500.0)}

    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=2)
    old, young = pool.session(), pool.session()
    old.advance(t_off)                  # only old's row ages
    old.submit(late_cfs)
    young.submit(base_cfs)              # young stays on the t=0 grid
    got_old, got_young = {}, {}
    for _ in range(200):
        pool.advance(5.0)
        got_old.update({d.handle: (d.cct, tuple(np.asarray(d.fct)
                                                - t_off))
                        for d in old.poll()})
        got_young.update({d.handle: (d.cct, tuple(d.fct))
                          for d in young.poll()})
        if not (old.num_live or young.num_live):
            break
    assert not (old.num_live or young.num_live)
    assert old._epoch >= REBASE_TICKS, "the old row never re-based"
    assert young._epoch == 0, "re-basing leaked onto the young row"
    assert got_old == want, "old row lost δ resolution"
    assert got_young == want, "young row's grid was perturbed"


def test_pool_heterogeneous_params_bitwise_vs_standalone():
    """Three tenants under THREE different SchedulerParams (pool
    default, huge start_threshold, 2x δ) on one slab: every tenant's
    completions are bitwise those of a standalone session running its
    own params — heterogeneity changes the stacked parameter rows,
    never the arithmetic."""
    slow = dataclasses.replace(PARAMS, start_threshold=1e9)
    coarse = dataclasses.replace(PARAMS, delta=2e-2)
    trio = [PARAMS, slow, coarse]
    workloads = [_coflows(30 + i, 4) for i in range(3)]

    def drive(sessions, advance_all):
        results = [dict(), dict(), dict()]
        for s, w in zip(sessions, workloads):
            s.submit(sorted(w, key=lambda c: (c.arrival, c.cid)))
        for _ in range(200):
            advance_all(sessions, 0.9)
            _harvest(results, sessions)
            if not any(s.num_live for s in sessions):
                return results
        raise RuntimeError("failed to drain")

    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=3)
    pooled_sessions = [pool.session(params=p) for p in trio]
    pooled = drive(pooled_sessions, lambda s, dt: pool.advance(dt))

    solo_sessions = [SaathSession(p, num_ports=PORTS, backend="jax")
                     for p in trio]

    def seq_advance(sessions, dt):
        for s in sessions:
            s.advance(dt)

    solo = drive(solo_sessions, seq_advance)
    assert pooled == solo
    # and the slow tenant really ran its own thresholds: its queue
    # never left 0 (nothing reaches 1e9 bytes)
    assert all(v["queue"] <= 0 for v in
               pooled_sessions[1].snapshot().values())


def test_pool_async_ctl_download_charged_once_at_sync_point():
    """ISSUE 8 satellite: under async dispatch a chain of K advances
    enqueues K dispatches but moves ZERO control bytes — the deferred
    (tick, finished) download is charged exactly once, at `_sync_ctl`
    time (the first poll), not per dispatch."""
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=2)
    assert pool._async                      # async is the default
    a = pool.session()
    # one huge flow: nothing completes, so poll gathers no rows and the
    # only download in play is the ctl mirror itself
    a.submit([Coflow(0, 0.0, [Flow(0, 0, 1, 500.0)])])
    pool.advance(0.5)                       # first upload + parked ctl
    base_ctl = pool.io["ctl_bytes"]
    base_disp = pool.io["dispatches"]
    for _ in range(5):
        pool.advance(0.5)                   # chain: re-park, no sync
    assert pool._ctl is not None
    assert pool.io["dispatches"] == base_disp + 5
    assert pool.io["ctl_bytes"] == base_ctl, \
        "async dispatch paid a ctl download at dispatch time"
    # the tick and completion mirrors, and the per-row int32 work
    # counters (`jax_engine.WorkCounts`) that ride the same download
    from repro.fabric.jax_engine import WorkCounts

    expect = pool._ticks.nbytes + pool._fin.nbytes \
        + len(WorkCounts._fields) * pool._ticks.nbytes
    assert a.poll() == []                   # the sync point
    assert pool._ctl is None                # handle consumed
    assert pool.io["ctl_bytes"] == base_ctl + expect, \
        "one chain of K advances must cost exactly ONE ctl download"
    assert a.poll() == []                   # no parked ctl: no charge
    assert pool.io["ctl_bytes"] == base_ctl + expect


# ---- the serving front door (launch.serve.CoflowServer) ----------------


def test_coflow_server_admission_results_and_eviction():
    from repro.launch.serve import AdmissionError, CoflowServer

    srv = CoflowServer(PARAMS, num_ports=PORTS, max_tenants=2)
    srv.register("alice")
    srv.register("bob")
    with pytest.raises(ValueError, match="already registered"):
        srv.register("alice")
    with pytest.raises(AdmissionError, match="admission cap"):
        srv.register("carol")
    assert srv.rejected == 1
    with pytest.raises(KeyError, match="unknown tenant"):
        srv.submit("carol", _coflows(1, 1))

    srv.submit("alice", _coflows(20, 3))
    srv.submit("bob", _coflows(21, 2))
    for _ in range(100):
        srv.advance(1.0)
        if not (srv.num_live("alice") or srv.num_live("bob")):
            break
    res = srv.result("alice")                # normalized per-tenant
    assert int(res.num_coflows[0]) == 3
    assert len(srv.poll("alice")) == 3       # result() is a pure
    assert srv.poll("alice") == []           # accessor; poll is once-each
    assert np.isfinite(res.avg_cct[0]) and np.isfinite(res.makespan[0])
    idle = srv.result("bob")
    assert int(idle.num_coflows[0]) == 2

    srv.evict("alice")
    srv.register("carol")                    # the freed row
    assert sorted(srv.tenants) == ["bob", "carol"]
    assert np.isnan(srv.result("carol").avg_cct[0])   # nothing yet
