"""Pallas TPU kernel for coflow contention k_c (the LCoF hot spot).

k_c = #other coflows sharing >=1 sender or receiver port with coflow c.

Shaped as an MXU problem: S = A_s A_s^T + A_r A_r^T over the (C, P)
{0,1} incidence matrices, then k_c = row-count of S > 0 (minus self).
The grid tiles (C x C) into (bc x bc) blocks; each step holds four
(bc, Pp) incidence strips, double-buffered, and adds a lane-dense
(1, bc) partial count to coflow block i's output. By that block
arithmetic (the compiler does not report it) VMEM is 8 * bc * Pp * 4 B:
2 MiB at the FB trace's 150 ports (Pp = 256), 4 MiB at table 2 (b)'s
512 and 31 MiB at ops.CONTENTION_MAX_P = 3968, under the 32 MiB limit
the call sets (v5e has 128 MiB). The v5e compiler accepts 3968 ports
and refuses 3969 (Pp = 4096) for lack of VMEM, which sets the domain.
Those compiles are kept as tests (tests/test_tpu_compile.py), and
chip_smoke.py checks the kernel against the reference on the chip at
4096 x 512 and 4096 x 3968. The kernel's time on the chip is not
measured yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 32 * 1024 * 1024


def _contention_kernel(a_s_i, a_r_i, a_s_j, a_r_j, k_ref, *, bc):
    i = pl.program_id(0)
    j = pl.program_id(1)
    # block (j, i) of S = A_s A_s^T + A_r A_r^T, contracted over ports
    # (an NT matmul, no in-kernel transpose). S is symmetric, so column
    # sums of block (j, i) are the row sums of block (i, j) and land
    # lane-dense as a (1, bc) row of coflow i's counts.
    nt = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(a_s_j[...], a_s_i[...], nt,
                            preferred_element_type=jnp.float32)
    s += jax.lax.dot_general(a_r_j[...], a_r_i[...], nt,
                             preferred_element_type=jnp.float32)
    blocks = (s > 0.5).astype(jnp.float32)   # (bc_j, bc_i) "c' blocks c"
    # on the diagonal block, remove self-contention
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 1)
    on_diag = (i == j) & (row_ids == col_ids)
    blocks = jnp.where(on_diag, 0.0, blocks)
    partial = blocks.sum(axis=0, keepdims=True)   # (1, bc_i)

    @pl.when(j == 0)
    def _init():
        k_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        k_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def contention_pallas(a_send: jax.Array, a_recv: jax.Array,
                      active: jax.Array, *, bc: int = 256,
                      interpret: bool = False) -> jax.Array:
    """a_send/a_recv: (C, P) float32 {0,1}; active: (C,) bool.

    Returns (C,) int32 contention counts (0 for inactive coflows).
    C and P are padded to multiples of (bc, 128) here; callers pass any
    shape.
    """
    C, P = a_send.shape
    Cp = -(-C // bc) * bc
    Pp = -(-P // 128) * 128
    act = active.astype(a_send.dtype)[:, None]
    a_s = jnp.zeros((Cp, Pp), a_send.dtype).at[:C, :P].set(a_send * act)
    a_r = jnp.zeros((Cp, Pp), a_recv.dtype).at[:C, :P].set(a_recv * act)

    grid = (Cp // bc, Cp // bc)
    strip = pl.BlockSpec((bc, Pp), lambda i, j: (i, 0))
    stripT = pl.BlockSpec((bc, Pp), lambda i, j: (j, 0))
    out = pl.BlockSpec((1, bc), lambda i, j: (0, i))
    k = pl.pallas_call(
        functools.partial(_contention_kernel, bc=bc),
        grid=grid,
        in_specs=[strip, strip, stripT, stripT],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((1, Cp), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(a_s, a_r, a_s, a_r)
    return jnp.where(active, k[0, :C].astype(jnp.int32), 0)
