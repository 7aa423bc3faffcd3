"""Operations and bytes of the scheduler's kernels, from the logical
operands each call receives (per lane, before any padding the kernel
adds), as the plain references `repro.kernels.ref.contention_ref` and
`maxmin_ref` compute them. A later change that drops padding or
replaces a kernel is read against the same work."""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks (bench/peaks.json); an unknown device
    is an error, not a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def contention_work(C: int, P: int) -> tuple:
    """(flops, bytes) of contention_ref on (C, P) f32 incidence: two
    C x C x P products, the threshold and the row count; reads both
    incidence matrices and the active mask, writes C int32 counts."""
    flops = 2 * (2 * C * C * P) + 3 * C * C
    nbytes = 2 * C * P * F32 + C + C * F32
    return flops, nbytes


def maxmin_work(P: int, F: int) -> tuple:
    """(flops, bytes) of maxmin_ref on (P, F) f32 one-hot incidence
    (ports, or ports then leaf links, by flows): 2P + 2 rounds, each
    with six P x F mat-vec products (the two active counts, the two
    saturated-row spreads, the two residual updates), 8F flow-wise
    (active mask of two, hit test of four, rate select, freeze) and
    22P row-wise operations (two levels of four, two minima, two
    saturation tests of three, two residual updates of three); reads
    both one-hots, the live mask and both bandwidths, writes F f32
    rates."""
    rounds = 2 * P + 2
    flops = rounds * (6 * 2 * P * F + 8 * F + 22 * P)
    nbytes = 2 * P * F * F32 + F + 2 * P * F32 + F * F32
    return flops, nbytes


WORK = {"contention": contention_work, "maxmin": maxmin_work}


def share(ctx, op: str, match: str):
    """A kernel's share of its roofline in the trace, in percent: the
    least time its calls need at the chip's peaks (the larger of flops
    over peak FLOP/s and bytes over peak bandwidth, summed over calls,
    each call covering the lanes of one chip's rows: all of the slab's,
    over the number of chips its rows are sharded on), over the kernel's
    device time, summed over chips. None where the trace has no call of
    the kernel."""
    evs = ctx.kernel_events(match)
    shape = ctx.kernel_shapes.get(op)
    if not evs or shape is None:
        return None
    pk = peaks(ctx.device_kind)
    flops, nbytes = WORK[op](*shape)
    least = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    t = sum(e - s for _, _, s, e in evs)
    return 100.0 * least * (ctx.lanes / ctx.n_devices) * len(evs) / t
