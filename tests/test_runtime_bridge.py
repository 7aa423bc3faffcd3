"""Coflow bridge / wave planner / barrier-issue properties."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.runtime.buckets import bucketize
from repro.runtime.coflow_bridge import (CollectiveCoflow,
                                         grad_bucket_coflows, plan_waves)
from repro.runtime.overlap import scheduled_psum


def test_bucketize_order_and_coverage():
    tree = {f"l{i}": jnp.zeros((128, 128)) for i in range(6)}
    bks = bucketize(tree, bucket_bytes=3 * 128 * 128 * 4)
    idx = [i for b in bks for i in b.leaf_idx]
    assert sorted(idx) == list(range(6))        # every leaf exactly once
    assert idx == idx[::-1][::-1] and idx[0] == 5  # reverse-layer order
    assert all(b.bytes <= 3 * 128 * 128 * 4 for b in bks)


@given(st.lists(st.sampled_from(["ici:data", "ici:model", "dcn", "host"]),
                min_size=1, max_size=3, unique=True),
       st.integers(2, 10))
@settings(max_examples=25, deadline=None)
def test_plan_waves_properties(res, n):
    rng = np.random.default_rng(0)
    coflows = [CollectiveCoflow(f"c{i}", int(rng.integers(1 << 20, 1 << 28)),
                                tuple(rng.choice(res, rng.integers(
                                    1, len(res) + 1), replace=False)),
                                i)
               for i in range(n)]
    waves = plan_waves(coflows, num_chips=8)
    flat = [c for w in waves for c in w]
    assert sorted(flat) == sorted(c.name for c in coflows)  # all, once
    # within a wave, coflows share no resource (all-or-none feasibility)
    by_name = {c.name: c for c in coflows}
    for w in waves:
        used = []
        for nme in w:
            for r in by_name[nme].resources:
                assert r not in used, (w, r)
                used.append(r)


def test_grad_buckets_serialize_lcof_orders_tenants():
    bks = bucketize({f"l{i}": jnp.zeros((64, 64)) for i in range(4)},
                    bucket_bytes=64 * 64 * 4)
    cfs = grad_bucket_coflows(bks)
    cfs += [CollectiveCoflow("bg/dcn", 1 << 30, ("dcn",), 99)]
    waves = plan_waves(cfs, num_chips=4)
    # grad buckets all on ici:data -> exactly one per wave, arrival order
    grads = [n for w in waves for n in w if n.startswith("grad/")]
    assert grads == [f"grad/{i}" for i in range(len(bks))]
    per_wave = [sum(n.startswith("grad/") for n in w) for w in waves]
    assert max(per_wave) == 1
    # the DCN tenant rides wave 0 (disjoint resource)
    assert "bg/dcn" in waves[0]


def test_plan_waves_colliding_ranks_keep_all_collectives():
    """Regression: two tenants built with the same rank_offset used to
    collide in the rank->position maps and silently drop collectives
    from the wave plan. Ranks are now densely renumbered preserving
    (rank, submission) order, so every collective is planned once."""
    bks = bucketize({f"l{i}": jnp.zeros((64, 64)) for i in range(3)},
                    bucket_bytes=64 * 64 * 4)
    tenant_a = grad_bucket_coflows(bks, rank_offset=0)
    tenant_b = grad_bucket_coflows(bks, axes=("ici:model",), rank_offset=0)
    tenant_b = [dataclasses.replace(c, name=f"b/{c.name}")
                for c in tenant_b]
    cfs = tenant_a + tenant_b + [
        CollectiveCoflow("bg/dcn", 1 << 30, ("dcn",), 0)]  # third collision
    waves = plan_waves(cfs, num_chips=4)
    flat = [n for w in waves for n in w]
    assert sorted(flat) == sorted(c.name for c in cfs), flat
    assert len(flat) == len(cfs)  # nothing dropped, nothing duplicated
    # serialization per resource still holds despite the collisions
    grads_a = [n for w in waves for n in w
               if n.startswith("grad/")]
    assert grads_a == [f"grad/{i}" for i in range(len(bks))]


def test_scheduled_psum_preserves_values_and_orders():
    from jax.sharding import Mesh, PartitionSpec as P

    tree = {"a": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones((8,))}
    bks = bucketize(tree, bucket_bytes=1 << 10)
    waves = [[f"grad/{b.bid}"] for b in bks]
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    flat, _ = jax.tree_util.tree_flatten(tree)

    def f(*g):
        return tuple(scheduled_psum(list(g), bks, waves, "data"))

    fn = jax.shard_map(f, mesh=mesh, in_specs=tuple(P() for _ in flat),
                       out_specs=tuple(P() for _ in flat))
    out = jax.jit(fn)(*flat)
    for a, b in zip(out, flat):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # issue order is enforced by optimization barriers in the stablehlo
    txt = jax.jit(fn).lower(*flat).as_text()
    assert txt.count("optimization_barrier") >= len(waves) - 1


def test_hlo_analysis_counts_loops():
    """Trip-count multipliers: a scanned matmul counts L x flops."""
    from benchmarks.hlo_analysis import analyze

    L, n = 7, 64
    w = jnp.ones((L, n, n))

    def f(x):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y

    hlo = jax.jit(f).lower(jnp.ones((n, n))).compile().as_text()
    res = analyze(hlo, 1)
    want = L * 2 * n ** 3
    assert 0.9 * want <= res["flops"] <= 1.2 * want, (res["flops"], want)
