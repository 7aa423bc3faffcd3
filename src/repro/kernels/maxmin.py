"""Pallas TPU kernel: bipartite max-min water-filling rate assignment.

Table 2 of the paper attributes most coordinator compute to assigning
work-conservation rates; this kernel runs the whole progressive-filling
solve in VMEM — one grid step, `2P` fixed rounds of dense mat-vec
products against the (P, F) one-hot incidence matrices (MXU work), no
HBM traffic between rounds.

Its domain is P <= 256 ports and F <= 4096 flows (ops.MAXMIN_MAX_P/F):
both (P, F) matrices are double-buffered in VMEM, 16 * P * F B, which
the v5e compiler puts at 16.2 MiB at the cap, inside the 32 MiB limit
the call sets; 512 ports or 8192 flows need 32.3-32.4 MiB and are
refused (tests/test_tpu_compile.py). chip_smoke.py checks the kernel
against the reference on the chip at the cap. Outside the domain, default
dispatch runs ref.maxmin_ref and a forced Pallas path raises. The
kernel's time on the chip is not measured yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30
VMEM_LIMIT = 32 * 1024 * 1024


def _maxmin_kernel(src_ref, dst_ref, live_ref, bws_ref, bwr_ref, rates_ref,
                   *, rounds):
    src = src_ref[...]          # (P, F) one-hot f32
    dst = dst_ref[...]
    live = live_ref[...]        # (1, F) f32 {0,1}

    def body(_, state):
        rates, frozen, avail_s, avail_r = state
        act = live * (1.0 - frozen)                       # (1, F)
        cnt_s = jnp.dot(src, act.T,
                        preferred_element_type=jnp.float32)  # (P, 1)
        cnt_r = jnp.dot(dst, act.T, preferred_element_type=jnp.float32)
        lvl_s = jnp.where(cnt_s > 0, avail_s / jnp.maximum(cnt_s, 1.0), BIG)
        lvl_r = jnp.where(cnt_r > 0, avail_r / jnp.maximum(cnt_r, 1.0), BIG)
        lvl = jnp.minimum(lvl_s.min(), lvl_r.min())
        sat_s = ((lvl_s <= lvl + 1e-12) & (cnt_s > 0)).astype(jnp.float32)
        sat_r = ((lvl_r <= lvl + 1e-12) & (cnt_r > 0)).astype(jnp.float32)
        inc = (jnp.dot(sat_s.T, src, preferred_element_type=jnp.float32)
               + jnp.dot(sat_r.T, dst,
                         preferred_element_type=jnp.float32))   # (1, F)
        hit = act * (inc > 0.5).astype(jnp.float32)
        rates = rates + lvl * hit
        avail_s = jnp.maximum(
            avail_s - lvl * jnp.dot(src, hit.T,
                                    preferred_element_type=jnp.float32), 0.0)
        avail_r = jnp.maximum(
            avail_r - lvl * jnp.dot(dst, hit.T,
                                    preferred_element_type=jnp.float32), 0.0)
        return rates, frozen + hit, avail_s, avail_r

    init = (jnp.zeros_like(live), 1.0 - live, bws_ref[...], bwr_ref[...])
    rates, _, _, _ = jax.lax.fori_loop(0, rounds, body, init)
    rates_ref[...] = rates


@functools.partial(jax.jit, static_argnames=("interpret",))
def maxmin_pallas(src_onehot: jax.Array, dst_onehot: jax.Array,
                  live: jax.Array, bw_send: jax.Array, bw_recv: jax.Array,
                  *, interpret: bool = False) -> jax.Array:
    """src/dst_onehot: (P, F) f32 {0,1}; live: (F,) bool; bw: (P,).

    Returns (F,) f32 max-min fair rates. Matches ref.maxmin_ref.
    """
    P, F = src_onehot.shape
    Pp = -(-P // 8) * 8
    Fp = -(-F // 128) * 128
    src = jnp.zeros((Pp, Fp), jnp.float32).at[:P, :F].set(src_onehot)
    dst = jnp.zeros((Pp, Fp), jnp.float32).at[:P, :F].set(dst_onehot)
    lv = jnp.zeros((1, Fp), jnp.float32).at[0, :F].set(
        live.astype(jnp.float32))
    bws = jnp.zeros((Pp, 1), jnp.float32).at[:P, 0].set(bw_send)
    bwr = jnp.zeros((Pp, 1), jnp.float32).at[:P, 0].set(bw_recv)

    rates = pl.pallas_call(
        functools.partial(_maxmin_kernel, rounds=2 * P + 2),
        grid=(1,),
        in_specs=[pl.BlockSpec((Pp, Fp), lambda _: (0, 0)),
                  pl.BlockSpec((Pp, Fp), lambda _: (0, 0)),
                  pl.BlockSpec((1, Fp), lambda _: (0, 0)),
                  pl.BlockSpec((Pp, 1), lambda _: (0, 0)),
                  pl.BlockSpec((Pp, 1), lambda _: (0, 0))],
        out_specs=pl.BlockSpec((1, Fp), lambda _: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, Fp), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(src, dst, lv, bws, bwr)
    return rates[0, :F]
