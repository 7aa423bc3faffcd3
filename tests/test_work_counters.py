"""Where a round's work and time go: the work counters the session loop
accumulates per row (`jax_engine.WorkCounts`, summed into `pool.io`),
the host spans of the pool and the front door, and the named scopes of
the compiled tick."""
import glob
import re

import jax
import numpy as np
import pytest

from repro.api import SessionPool
from repro.api import pool as pool_mod
from repro.core import jax_coordinator as jc
from repro.core.coflow import Coflow, Flow
from repro.core.params import SchedulerParams
from repro.fabric import jax_engine as je
from repro.launch import serve

PORTS = 6
PARAMS = SchedulerParams(port_bw=1.0, delta=1e-2, start_threshold=4.0,
                         growth=4.0, num_queues=5)
WORK = ("event_steps", "lane_steps", "admit_trips", "wc_trips",
        "wc_fills")


def _work(pool) -> dict:
    return {k: pool.io[k] for k in WORK}


def _two_tenants(async_dispatch: bool):
    """Tenant a: coflow 0 (one flow 0->1) and coflow 1 (flows 0->2 and
    3->4) both want sender port 0 at t=0. Coflow 0 leads the order (same
    queue and contention, earlier arrival) and takes port 0 whole, so
    coflow 1 is missed: 2 admission trips, and its 2 live flows are the
    work-conservation candidates (2 trips). Flow 0->2 finds port 0
    exhausted; flow 3->4 gets a rate (1 fill). Tenant b: one coflow (one
    flow 5->0), admitted: 1 admission trip, no candidate. Flows of 500
    bytes at 1 byte/s finish in no early tick."""
    pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=2,
                       async_dispatch=async_dispatch)
    a, b = pool.session(), pool.session()
    a.submit([Coflow(0, 0.0, [Flow(0, 0, 1, 500.0)]),
              Coflow(1, 0.0, [Flow(0, 0, 2, 500.0),
                              Flow(1, 3, 4, 500.0)])])
    b.submit([Coflow(0, 0.0, [Flow(0, 5, 0, 500.0)])])
    return pool, a, b


@pytest.mark.parametrize("async_dispatch", [True, False])
def test_work_counters_exact_on_a_hand_built_two_tenant_pool(
        async_dispatch):
    pool, a, b = _two_tenants(async_dispatch)
    pool.advance(PARAMS.delta)              # one tick: one event step
    assert a.poll() == [] and b.poll() == []
    assert _work(pool) == dict(event_steps=1, lane_steps=2,
                               admit_trips=3, wc_trips=2, wc_fills=1)
    snap = a.snapshot()
    assert [snap[h]["running"] for h in sorted(snap)] == [True, False]


def test_work_counters_identical_between_blocking_and_async():
    """A chain of async advances accumulates the counters on the
    device and downloads them once, at the next sync; the blocking path
    downloads them per dispatch. Both count the same work."""
    def run(async_dispatch):
        pool = SessionPool(PARAMS, num_ports=PORTS, max_sessions=4,
                           async_dispatch=async_dispatch)
        rng = np.random.default_rng(7)
        sessions = [pool.session() for _ in range(3)]
        for i, s in enumerate(sessions):
            s.submit([Coflow(c, float(rng.uniform(0, 1.0)),
                             [Flow(j, int(rng.integers(0, PORTS)),
                                   int(rng.integers(0, PORTS)),
                                   float(rng.uniform(1.0, 8.0)))
                              for j in range(1 + (c + i) % 3)])
                      for c in range(4)])
        for step in range(12):
            pool.advance(0.4)
            if step % 3 == 2:               # chains of three advances
                pool.poll()
        pool.poll()
        return _work(pool), pool.io["dispatches"]

    got, blocking = run(True), run(False)
    assert got == blocking
    assert got[0]["event_steps"] > 0 and got[0]["wc_trips"] > 0
    assert 0 < got[0]["wc_fills"] <= got[0]["wc_trips"]


def test_host_spans_nest_on_the_profiler_clock(tmp_path):
    """A CPU profiler trace of one served round holds the front door's
    and the pool's spans, nested as the calls nest, and each dispatch's
    sync carries the number of the dispatch it waited on."""
    from jax.profiler import ProfileData

    srv = serve.CoflowServer(PARAMS, num_ports=PORTS, max_tenants=2)
    srv.register("a")
    srv.submit("a", [Coflow(0, 0.0, [Flow(0, 0, 1, 0.5)])])
    srv.advance(1.0)                        # compile outside the trace
    assert len(srv.poll("a")) == 1
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.submit("a", [Coflow(1, 1.0, [Flow(0, 1, 2, 0.5)])])
        srv.advance(1.0)
        done = srv.poll("a")
    finally:
        jax.profiler.stop_trace()
    assert [d.handle for d in done] == [1]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("saath.")]
    names = [n for n, *_ in spans]

    def one(name):
        hits = [sp for sp in spans if sp[0] == name]
        assert len(hits) == 1, (name, names)
        return hits[0]

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    adv = one(serve.SPAN_ADVANCE)
    assert not inside(one(serve.SPAN_SUBMIT), adv)
    for name in (pool_mod.SPAN_STAGE, pool_mod.SPAN_UPLOAD,
                 pool_mod.SPAN_DISPATCH, pool_mod.SPAN_SYNC_CTL,
                 serve.SPAN_ADMIT_DEFERRED):
        assert inside(one(name), adv), name
    disp, sync = one(pool_mod.SPAN_DISPATCH), one(pool_mod.SPAN_SYNC_CTL)
    assert disp[2] <= sync[1]               # enqueue, then wait
    assert disp[3]["dispatch"] == sync[3]["dispatch"] == \
        srv.pool.io["dispatches"]
    harvests = [sp for sp in spans if sp[0] == serve.SPAN_HARVEST]
    gather = one(pool_mod.SPAN_GATHER)      # the completion's row
    assert any(inside(gather, h) and inside(h, adv) for h in harvests)


def test_every_scope_names_ops_of_the_compiled_session_loop():
    """The named scopes reach the ops' metadata (`op_name`) of the
    session block: what a device trace's ops are attributed by."""
    from repro.analysis.audit import FEATURES, _canonical_slab

    tb, _, ep_rows, state = _canonical_slab()
    ne = np.full(state.tick.shape, 4.0, np.float32)
    hlo = je._run_session_block.lower(
        state, tb, ep_rows, ne, np.int32(64), kernel=None,
        features=FEATURES).compile().as_text()
    # under vmap a scope reads `.../vmap(saath.tick.admit)/...`
    found = {m for name in re.findall(r'op_name="([^"]*)"', hlo)
             for m in re.findall(r"saath\.[\w.]+", name)}
    assert found == {je.SCOPE_SESSION, je.SCOPE_VIEWS, je.SCOPE_HORIZON,
                     jc.SCOPE_QUEUES, jc.SCOPE_CONTENTION, jc.SCOPE_ORDER,
                     jc.SCOPE_ADMIT, jc.SCOPE_WC_ORDER, jc.SCOPE_WC_FILL}
