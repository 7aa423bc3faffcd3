"""The jitted coordinator agrees with the numpy Saath reference."""
import numpy as np
from hypothesis import example, given, settings

from repro.core.coflow import Coflow, Flow, Trace
from repro.core.params import SchedulerParams
from repro.core.policies import make_policy
from repro.fabric.engine import Simulator
from repro.fabric.state import FlowTable

from tests.test_properties import PARAMS, mid_state, traces


@given(traces())
@settings(max_examples=30, deadline=None)
def test_admission_matches_numpy(trace):
    """All-or-none admission rates: jitted tick == numpy Fig. 7 loop."""
    t = mid_state(trace)
    ref = make_policy("saath", PARAMS, work_conservation=False)
    ref.reset(t)
    want = ref.schedule(t, 1.0)

    jaxp = make_policy("saath-jax", PARAMS, work_conservation=False)
    jaxp.reset(t)
    got = jaxp.schedule(t, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@given(traces())
@settings(max_examples=15, deadline=None)
# three coflows on port 0->0, one exactly at start_threshold: the
# saath-jax policy's known fidelity gap against numpy (ROADMAP §3)
@example(trace=Trace(num_ports=6, coflows=[
    Coflow(cid=0, arrival=1.0,
           flows=[Flow(fid=f, src=0, dst=0, size=1.0) for f in range(5)]),
    Coflow(cid=1, arrival=0.0,
           flows=[Flow(fid=f, src=0, dst=0, size=1.0) for f in (5, 6)]),
    Coflow(cid=2, arrival=0.0,
           flows=[Flow(fid=7, src=0, dst=0, size=4.0)])]))
def test_full_sim_close_to_numpy(trace):
    """End-to-end on the FULL reference config (per-flow work
    conservation + §4.3 dynamics re-queue, both defaults): the jitted
    coordinator's replay matches the numpy reference's average CCT
    within 1% — the former 2x coflow-granularity envelope is closed."""
    ta = FlowTable.from_trace(trace, PARAMS.port_bw)
    ra = Simulator(PARAMS).run(ta, make_policy("saath", PARAMS))
    tb = FlowTable.from_trace(trace, PARAMS.port_bw)
    rb = Simulator(PARAMS).run(tb, make_policy("saath-jax", PARAMS))
    assert rb.table.finished.all()
    a = float(np.nanmean(ra.table.cct))
    b = float(np.nanmean(rb.table.cct))
    assert abs(b - a) <= 1e-2 * a + 2 * PARAMS.delta


def mixed_state(trace, frac=0.5):
    """A state where some flows FINISHED and some are live — the §4.3
    re-queue trigger — with every coflow keeping >= 1 live flow."""
    t = FlowTable.from_trace(trace, PARAMS.port_bw)
    rng = np.random.default_rng(1)
    t.sent = t.size * rng.uniform(0, 1, t.size.shape) * 0.5
    done = rng.uniform(size=t.size.shape) < frac
    for c in range(t.num_coflows):
        lo, hi = t.flow_lo[c], t.flow_hi[c]
        if done[lo:hi].all():
            done[lo] = False
    t.done[:] = done
    t.sent[done] = t.size[done]
    t.fct[done] = 0.5
    t.active[:] = True
    return t


@given(traces())
@settings(max_examples=30, deadline=None)
def test_requeue_matches_numpy(trace):
    """§4.3 re-queue: on randomized mixed done/live tables the jitted
    tick's queue assignment (median-estimated remaining length, Eq. 1)
    equals the numpy Saath._assign_queues."""
    t = mixed_state(trace)
    ref = make_policy("saath", PARAMS)
    ref.reset(t)
    want_q = ref._assign_queues(t, 1.0)
    jaxp = make_policy("saath-jax", PARAMS)
    jaxp.reset(t)
    jaxp.schedule(t, 1.0)
    got_q = np.asarray(jaxp._last_out["queue"])[:t.num_coflows]
    np.testing.assert_array_equal(got_q, want_q)


@given(traces())
@settings(max_examples=30, deadline=None)
def test_per_flow_wc_rates_match_numpy(trace):
    """Full-config single tick on mixed done/live tables: admission +
    per-flow work conservation + §4.3 re-queue — the per-FLOW rates
    (a strict subset of a missed coflow's flows may be rescued) equal
    the numpy reference's greedy_flow_alloc fill."""
    t = mixed_state(trace)
    ref = make_policy("saath", PARAMS)
    ref.reset(t)
    want = ref.schedule(t, 1.0)
    jaxp = make_policy("saath-jax", PARAMS)
    jaxp.reset(t)
    got = jaxp.schedule(t, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_jax_coordinator_states_roll_forward():
    """Deadlines and queues persist across ticks (stateless-restart also
    re-derivable, mirroring the paper's stateless coordinator)."""
    import jax.numpy as jnp

    from repro.core import jax_coordinator as jc

    cp = jc.CoordParams.from_params(SchedulerParams(port_bw=1.0))
    C, P = 8, 4
    state = jc.init_state(C)
    rng = np.random.default_rng(0)
    batch = jc.CoflowBatch(
        active=jnp.asarray(np.ones(C, bool)),
        arrival=jnp.arange(C, dtype=jnp.int32),
        m=jnp.zeros(C, jnp.float32),
        width=jnp.ones(C, jnp.int32),
        cnt_s=jnp.asarray((rng.uniform(size=(C, P)) < 0.4).astype(np.float32)),
        cnt_r=jnp.asarray((rng.uniform(size=(C, P)) < 0.4).astype(np.float32)),
        bw_s=jnp.ones(P, jnp.float32),
        bw_r=jnp.ones(P, jnp.float32),
    )
    s1, o1 = jc.schedule_tick(state, batch, jnp.float32(0.0), cp=cp)
    assert np.isfinite(np.asarray(s1.deadline)).all()
    s2, o2 = jc.schedule_tick(s1, batch, jnp.float32(0.5), cp=cp)
    # same fabric, same tick inputs -> stable admission (no churn)
    np.testing.assert_array_equal(np.asarray(o1["admitted"]),
                                  np.asarray(o2["admitted"]))
    # deadlines unchanged when queues did not change
    np.testing.assert_allclose(np.asarray(s1.deadline),
                               np.asarray(s2.deadline))
