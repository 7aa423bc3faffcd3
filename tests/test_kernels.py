"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------- contention
@pytest.mark.parametrize("C,P", [(3, 5), (64, 64), (130, 150), (257, 96),
                                 (512, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_contention_sweep(C, P, dtype):
    a_s = jnp.asarray((RNG.uniform(size=(C, P)) < 0.15), dtype)
    a_r = jnp.asarray((RNG.uniform(size=(C, P)) < 0.15), dtype)
    act = jnp.asarray(RNG.uniform(size=C) < 0.8)
    got = ops.contention(a_s, a_r, act, force="interpret")
    want = ref.contention_ref(a_s.astype(jnp.float32),
                              a_r.astype(jnp.float32), act)
    np.testing.assert_array_equal(np.array(got), np.array(want))
    # cross-check vs the numpy scheduler reference
    from repro.core.contention import contention as np_contention
    want_np = np_contention(np.array(a_s, np.float32) > 0.5,
                            np.array(a_r, np.float32) > 0.5, np.array(act))
    np.testing.assert_array_equal(np.array(got), want_np)


def test_contention_all_inactive():
    a = jnp.zeros((8, 8), jnp.float32)
    act = jnp.zeros(8, bool)
    got = ops.contention(a, a, act, force="interpret")
    assert (np.array(got) == 0).all()


# ------------------------------------------------------------------- maxmin
@pytest.mark.parametrize("P,F", [(2, 3), (6, 30), (16, 128), (32, 200)])
def test_maxmin_sweep(P, F):
    src_i = RNG.integers(0, P, F)
    dst_i = RNG.integers(0, P, F)
    live = jnp.asarray(RNG.uniform(size=F) < 0.85)
    S = np.zeros((P, F), np.float32)
    S[src_i, np.arange(F)] = 1
    D = np.zeros((P, F), np.float32)
    D[dst_i, np.arange(F)] = 1
    bw = jnp.asarray(RNG.uniform(0.5, 2.0, P), jnp.float32)
    got = ops.maxmin_rates(jnp.asarray(S), jnp.asarray(D), live, bw, bw,
                           force="interpret")
    want = ref.maxmin_ref(jnp.asarray(S), jnp.asarray(D), live, bw, bw)
    np.testing.assert_allclose(np.array(got), np.array(want), atol=1e-5)
    # invariants: capacity respected, dead flows get nothing
    np.testing.assert_array_less(S @ np.array(got), np.array(bw) + 1e-4)
    assert (np.array(got)[~np.array(live)] == 0).all()


@pytest.mark.parametrize("force", ["pallas", "interpret"])
def test_forced_kernel_outside_its_domain_raises(force):
    P, F = ops.MAXMIN_MAX_P + 1, 8
    with pytest.raises(ValueError, match="outside the Pallas kernel"):
        ops.maxmin_rates(jnp.zeros((P, F)), jnp.zeros((P, F)),
                         jnp.zeros((F,), bool), jnp.ones(P), jnp.ones(P),
                         force=force)
    C, P = 4, ops.CONTENTION_MAX_P + 1
    with pytest.raises(ValueError, match="outside the Pallas kernel"):
        ops.contention(jnp.zeros((C, P)), jnp.zeros((C, P)),
                       jnp.ones(C, bool), force=force)


def test_default_dispatch_records_the_path_it_took():
    """Off TPU, and past a kernel's domain anywhere, default dispatch
    runs the reference, and says so."""
    P, F = ops.MAXMIN_MAX_P + 1, 8
    with ops.record_paths() as log:
        ops.maxmin_rates(jnp.zeros((P, F)), jnp.zeros((P, F)),
                         jnp.zeros((F,), bool), jnp.ones(P), jnp.ones(P))
        ops.contention(jnp.zeros((4, 6)), jnp.zeros((4, 6)),
                       jnp.ones(4, bool), force="interpret")
    assert log == [("maxmin", (P, F), "ref"),
                   ("contention", (4, 6), "interpret")]


def test_maxmin_matches_numpy_waterfill():
    from repro.core.policies.base import maxmin_waterfill
    from repro.fabric.state import FlowTable
    from repro.traces import tiny_trace

    tr = tiny_trace(12, 8, seed=3)
    t = FlowTable.from_trace(tr, 1.0)
    t.active[:] = True
    live = t.flow_live()
    F, P = t.size.shape[0], t.num_ports
    S = np.zeros((P, F), np.float32)
    S[t.src, np.arange(F)] = 1
    D = np.zeros((P, F), np.float32)
    D[t.dst, np.arange(F)] = 1
    got = ops.maxmin_rates(jnp.asarray(S), jnp.asarray(D), jnp.asarray(live),
                           jnp.asarray(t.bw_send, jnp.float32),
                           jnp.asarray(t.bw_recv, jnp.float32),
                           force="interpret")
    want = maxmin_waterfill(t, live)
    np.testing.assert_allclose(np.array(got), want, atol=1e-5)


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "B,H,Hkv,S,T,D", [(1, 1, 1, 16, 16, 32), (2, 4, 2, 64, 64, 64),
                      (1, 8, 1, 32, 32, 128), (1, 2, 2, 40, 40, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, Hkv, S, T, D, dtype, causal):
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, T, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, T, D)), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, bq=16, bk=16,
                              force="interpret")
    want = ref.attention_ref(q, k, v, causal=causal)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(want, np.float32), atol=atol)


def test_flash_attention_chunked_prefill_offset():
    """Chunked prefill: attending with q_offset equals slicing the full
    causal result."""
    B, H, S, D = 1, 2, 64, 32
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    full = ops.flash_attention(q, k, v, causal=True, bq=16, bk=16,
                               force="interpret")
    half = ops.flash_attention(q[:, :, 32:], k, v, causal=True, bq=16,
                               bk=16, q_offset=32, force="interpret")
    np.testing.assert_allclose(np.array(half), np.array(full[:, :, 32:]),
                               atol=1e-5)


# ----------------------------------------------------------------- ssd scan
@pytest.mark.parametrize(
    "B,L,H,G,Dh,N,lc", [(1, 16, 1, 1, 8, 8, 8), (2, 64, 4, 2, 16, 32, 16),
                        (1, 128, 2, 1, 32, 64, 64), (1, 256, 8, 2, 64, 128,
                                                     128)])
def test_ssd_scan_sweep(B, L, H, G, Dh, N, lc):
    x = jnp.asarray(RNG.normal(size=(B, L, H, Dh)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(B, L, H)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.3, 2.0, size=H), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    got_y, got_s = ops.ssd_scan(x, dt, a, b, c, lc=lc, force="interpret")
    want_y, want_s = ref.ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(np.array(got_y), np.array(want_y),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.array(got_s), np.array(want_s),
                               atol=5e-4, rtol=1e-3)


def test_ssd_scan_state_chaining():
    """Running two halves with carried state == one full scan (the decode /
    multi-step serving contract)."""
    B, L, H, G, Dh, N = 1, 64, 2, 1, 16, 32
    x = jnp.asarray(RNG.normal(size=(B, L, H, Dh)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(B, L, H)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.3, 2.0, size=H), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    y_full, s_full = ops.ssd_scan(x, dt, a, b, c, lc=16, force="interpret")
    y1, s1 = ops.ssd_scan(x[:, :32], dt[:, :32], a, b[:, :32], c[:, :32],
                          lc=16, force="interpret")
    y2, s2 = ops.ssd_scan(x[:, 32:], dt[:, 32:], a, b[:, 32:], c[:, 32:],
                          init_state=s1, lc=16, force="interpret")
    np.testing.assert_allclose(np.array(jnp.concatenate([y1, y2], 1)),
                               np.array(y_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.array(s2), np.array(s_full), atol=1e-4,
                               rtol=1e-3)
