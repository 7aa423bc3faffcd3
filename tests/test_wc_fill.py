"""The per-flow work-conservation greedy fill (`jax_coordinator.
_greedy_fill`): bit for bit the sequential walk over every candidate
flow, the numpy reference's `greedy_flow_alloc`, and one loop trip per
flow given a rate (at most one per port or link)."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.pool import SessionPool
from repro.core import jax_coordinator as jc
from repro.core.coflow import Coflow, Flow
from repro.core.params import SchedulerParams
from repro.core.policies import make_policy
from repro.core.policies.base import greedy_flow_alloc
from repro.fabric.topology import ExtraLinks, LeafSpine
from repro.traces.synth import tiny_trace

from tests.test_jax_coordinator import mixed_state
from tests.test_properties import PARAMS

P_MAX, F, LF = 8, 96, 3


@jax.jit
def _walk(flist, n_cand, avails, idxs):
    """The full walk the jump replaced: every candidate, in order, takes
    max(min(residuals), 0) and subtracts it from each resource."""
    F = flist.shape[0]

    def body(s):
        i, avails, wcf = s
        f = flist[i]
        at = [idx[f] for idx in idxs]
        r = functools.reduce(jnp.minimum,
                             [a[j] for a, j in zip(avails, at)])
        r = jnp.maximum(r, 0.0)
        return (i + 1, tuple(a.at[j].add(-r) for a, j in zip(avails, at)),
                wcf.at[f].set(r))

    _, _, wcf = jax.lax.while_loop(
        lambda s: s[0] < n_cand, body,
        (jnp.int32(0), tuple(avails), jnp.zeros((F,), jnp.float32)))
    return wcf


@jax.jit
def _jump(flist, n_cand, avails, idxs):
    return jc._greedy_fill(flist, n_cand, list(zip(avails, idxs)))


def _residuals(rng, n, dyadic):
    """Residuals mixing positive, exhausted (0) and overdrawn (< 0)
    values; dyadic ones keep f32 and f64 arithmetic exact."""
    if dyadic:
        pos = rng.integers(1, 33, n) / 8.0
    else:
        pos = rng.uniform(1e-3, 4.0, n)
    kind = rng.integers(0, 5, n)
    return np.where(kind == 0, 0.0,
                    np.where(kind == 1, -rng.integers(1, 9, n) / 8.0,
                             pos)).astype(np.float32)


def _case(seed, leaf_spine, dyadic=False):
    """A random fill at fixed shapes (one compile): up to 8 ports for 96
    flows (shared ports), a random priority order and candidate count;
    on a leaf-spine fabric, per-flow uplink/downlink ids in [0, LF] with
    LF the BIG sentinel slot."""
    rng = np.random.default_rng(seed)
    P = int(rng.integers(1, P_MAX + 1))     # ports in use
    src = rng.integers(0, P, F).astype(np.int32)
    dst = rng.integers(0, P, F).astype(np.int32)
    flist = rng.permutation(F).astype(np.int32)
    n_cand = int(rng.integers(0, F + 1))
    avails = [_residuals(rng, P_MAX, dyadic),
              _residuals(rng, P_MAX, dyadic)]
    idxs = [src, dst]
    if leaf_spine:
        for _ in range(2):
            avails.append(np.append(_residuals(rng, LF, dyadic),
                                    np.float32(jc.BIG)))
            idxs.append(rng.integers(0, LF + 1, F).astype(np.int32))
    return dict(P=P, Lf=LF if leaf_spine else 0, flist=flist,
                n_cand=n_cand, avails=tuple(avails), idxs=tuple(idxs))


def _run_jump(c):
    got, n_fill = _jump(c["flist"], np.int32(c["n_cand"]), c["avails"],
                        c["idxs"])
    return np.asarray(got), int(n_fill)


@pytest.mark.parametrize("leaf_spine", [False, True],
                         ids=["big_switch", "leaf_spine"])
@pytest.mark.parametrize("block", range(4))
def test_jump_bitwise_equals_the_full_walk(leaf_spine, block):
    fills = cands = 0
    for seed in range(100 * block, 100 * block + 100):
        c = _case(seed, leaf_spine)
        want = np.asarray(_walk(c["flist"], np.int32(c["n_cand"]),
                                c["avails"], c["idxs"]))
        got, n_fill = _run_jump(c)
        # equal up to the sign of a zero (`==` treats -0.0 as 0.0)
        np.testing.assert_array_equal(got, want, err_msg=f"seed {seed}")
        assert n_fill == int((got > 0).sum()), seed
        assert n_fill <= c["n_cand"], seed
        # each fill saturates a port or link for good (the sentinel
        # slot never is): at most 2P + 2Lf trips
        assert n_fill <= 2 * c["P"] + 2 * c["Lf"], seed
        fills, cands = fills + n_fill, cands + c["n_cand"]
    # the cases both fill and skip candidates
    assert 0 < fills < cands


@pytest.mark.parametrize("leaf_spine", [False, True],
                         ids=["big_switch", "leaf_spine"])
def test_jump_equals_numpy_greedy_flow_alloc(leaf_spine):
    for seed in range(200):
        c = _case(seed, leaf_spine, dyadic=True)
        flist = c["flist"]
        live = np.zeros(F, bool)
        live[flist[:c["n_cand"]]] = True
        src, dst = c["idxs"][:2]
        table = types.SimpleNamespace(size=np.ones(F), src=src, dst=dst,
                                      num_ports=P_MAX)
        extra = avail_x = None
        if leaf_spine:
            up, dn = c["idxs"][2:]
            extra = ExtraLinks(
                cap=None, up=np.where(up < LF, up, -1),
                dn=np.where(dn < LF, dn + LF, -1), num_uplinks=LF)
            avail_x = np.concatenate(
                [c["avails"][2][:LF], c["avails"][3][:LF]]).astype(
                    np.float64)
        want = greedy_flow_alloc(
            table, flist, live, c["avails"][0].astype(np.float64),
            c["avails"][1].astype(np.float64), extra=extra,
            avail_x=avail_x)
        got, _ = _run_jump(c)
        np.testing.assert_array_equal(got, want, err_msg=f"seed {seed}")


def test_vmapped_lanes_match_one_lane_at_a_time():
    """Under vmap the loop runs the busiest lane's trips; a lane done
    early must come out as if run alone."""
    P, B = 5, 6
    rng = np.random.default_rng(3)
    lanes = [dict(flist=rng.permutation(F).astype(np.int32),
                  n_cand=np.int32(rng.integers(0, F + 1)),
                  avails=(_residuals(rng, P, False),
                          _residuals(rng, P, False)),
                  idxs=(rng.integers(0, P, F).astype(np.int32),
                        rng.integers(0, P, F).astype(np.int32)))
             for _ in range(B)]
    stack = jax.tree_util.tree_map(lambda *x: np.stack(x), *lanes)
    got, fills = jax.vmap(_jump)(stack["flist"], stack["n_cand"],
                                 stack["avails"], stack["idxs"])
    for b, lane in enumerate(lanes):
        want, n_fill = _jump(lane["flist"], lane["n_cand"],
                             lane["avails"], lane["idxs"])
        np.testing.assert_array_equal(np.asarray(got[b]), want)
        assert int(fills[b]) == int(n_fill)


@pytest.mark.parametrize("seed", range(4))
def test_tick_fill_count_bounds(seed):
    """A whole tick: the fill's count equals the flows it gave a rate,
    never passes its candidates nor 2P, and the per-flow rates still
    equal the numpy reference's."""
    t = mixed_state(tiny_trace(24, 8, seed=seed, load=0.9), frac=0.3)
    ref = make_policy("saath", PARAMS)
    ref.reset(t)
    want = ref.schedule(t, 1.0)
    pol = make_policy("saath-jax", PARAMS)
    pol.reset(t)
    got = pol.schedule(t, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    out = pol._last_out
    n_fill, n_cand = int(out["n_fill"]), int(out["n_cand"])
    assert n_fill == int((np.asarray(out["wc_flow"]) > 0).sum())
    assert 0 < n_fill <= n_cand
    assert n_fill <= 2 * t.num_ports


def test_leaf_spine_pool_fill_counts():
    """An oversubscribed leaf-spine pool: every open lane step's fill
    gives at most 2P + 2L flows a rate, never more than its
    candidates."""
    ports, hosts = 16, 4
    params = SchedulerParams(port_bw=1.0, delta=1e-2)
    pool = SessionPool(params, num_ports=ports, max_sessions=2,
                       topology=LeafSpine(hosts_per_leaf=hosts,
                                          oversub=4.0))
    rng = np.random.default_rng(5)
    for _ in range(2):
        pool.session().submit([
            Coflow(c, float(rng.uniform(0, 0.05)),
                   [Flow(j, int(rng.integers(0, ports)),
                         int(rng.integers(0, ports)),
                         float(rng.uniform(0.05, 0.5)))
                    for j in range(int(rng.integers(1, 6)))])
            for c in range(12)])
    for _ in range(6):
        pool.advance(0.05)
    pool.poll()
    io = pool.io
    links = 2 * (ports // hosts)
    assert 0 < io["wc_fills"] <= io["wc_trips"]
    assert io["wc_fills"] <= (2 * ports + links) * io["lane_steps"]

