"""Public jit'd entry points for the Pallas kernels.

Each op dispatches: TPU -> compiled Pallas kernel; everywhere else ->
the pure-jnp oracle in ref.py (identical semantics, lowerable on any
backend — this is what the CPU dry-run and the smoke tests compile).
Set ``force='pallas'`` / ``force='ref'`` / ``force='interpret'`` to pin
a path (tests use 'interpret' to execute the kernel body on CPU).

Kernel domains. The scheduler kernels hold whole operands in VMEM, so
each compiles only up to a size (tests/test_tpu_compile.py compiles the
limits for v5e):

- ``contention``: padded ports P <= CONTENTION_MAX_P (any C: the grid
  tiles coflows);
- ``maxmin_rates``: P <= MAXMIN_MAX_P and F <= MAXMIN_MAX_F (one grid
  step holds both (P, F) incidence matrices).

Outside its domain, default dispatch runs the reference; an explicit
``force='pallas'``/``'interpret'`` raises instead. Either way the path
is visible: inside ``with record_paths() as log``, every trace of an op
appends ``(op, shape, path)`` to ``log``.
"""
from __future__ import annotations

import contextlib

import jax

from repro.kernels import ref
from repro.kernels.contention import contention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.maxmin import maxmin_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

CONTENTION_MAX_P = 3968
MAXMIN_MAX_P = 256
MAXMIN_MAX_F = 4096

_logs: list[list] = []


@contextlib.contextmanager
def record_paths():
    """Collect ``(op, operand shape, path)`` for every scheduler op
    traced inside the block. Recorded at trace time: a call served from
    a compiled executable adds nothing, so a caller that wants every op
    of a run clears jax's caches first (chip_smoke.py does)."""
    log: list[tuple[str, tuple, str]] = []
    _logs.append(log)
    try:
        yield log
    finally:
        _logs.remove(log)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _path(op: str, shape: tuple, force: str | None,
          in_domain: bool = True) -> str:
    if force == "ref":
        p = "ref"
    elif force is None:
        p = "pallas" if _on_tpu() and in_domain else "ref"
    elif not in_domain:
        raise ValueError(
            f"{op}: shape {shape} is outside the Pallas kernel's domain "
            f"(see repro.kernels.ops); force='{force}' cannot run it — "
            f"use force='ref' or default dispatch")
    else:
        p = force
    for log in _logs:
        log.append((op, tuple(shape), p))
    return p


def contention(a_send, a_recv, active, *, force: str | None = None):
    C, P = a_send.shape
    p = _path("contention", (C, P), force, P <= CONTENTION_MAX_P)
    if p == "ref":
        return ref.contention_ref(a_send, a_recv, active)
    return contention_pallas(a_send, a_recv, active,
                             interpret=(p == "interpret"))


def maxmin_rates(src_onehot, dst_onehot, live, bw_send, bw_recv, *,
                 force: str | None = None):
    P, F = src_onehot.shape
    p = _path("maxmin", (P, F), force,
              P <= MAXMIN_MAX_P and F <= MAXMIN_MAX_F)
    if p == "ref":
        return ref.maxmin_ref(src_onehot, dst_onehot, live, bw_send, bw_recv)
    return maxmin_pallas(src_onehot, dst_onehot, live, bw_send, bw_recv,
                         interpret=(p == "interpret"))


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    force: str | None = None, **kw):
    p = _path("flash_attention", q.shape, force)
    if p == "ref":
        assert q_offset == 0, "ref path is offset-free (full prefill)"
        return ref.attention_ref(q, k, v, causal=causal)
    return flash_attention_pallas(q, k, v, causal=causal, q_offset=q_offset,
                                  interpret=(p == "interpret"), **kw)


def ssd_scan(x, dt, a, b, c, *, init_state=None, force: str | None = None,
             **kw):
    p = _path("ssd_scan", x.shape, force)
    if p == "ref":
        return ref.ssd_ref(x, dt, a, b, c, init_state=init_state)
    return ssd_scan_pallas(x, dt, a, b, c, init_state=init_state,
                           interpret=(p == "interpret"), **kw)


__all__ = ["contention", "maxmin_rates", "flash_attention", "ssd_scan",
           "record_paths", "CONTENTION_MAX_P", "MAXMIN_MAX_P",
           "MAXMIN_MAX_F"]
