"""The reduction from a chip trace to the per-layer numbers, on three
recorded rounds of a 256-tenant fleet of 24-port pods on one TPU v5e
(data/pods_trace.json: device ops and the harness's spans, trimmed).
The reduction reads any cell's trace alike."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import roofline, spec, trace

DATA = json.loads((Path(__file__).parent / "data" / "pods_trace.json")
                  .read_text())
SHAPES = {"contention": (64, 24)}


def _ctx(io_bytes=3_000_000):
    ops = [("/device:TPU:0", n, s * 1e-9, e * 1e-9)
           for n, s, e in DATA["ops"]]
    spans = [(n, s * 1e-9, e * 1e-9) for n, s, e in DATA["spans"]]
    return trace.Context(ops, spans, rounds=3,
                         io=({"upload_bytes": 0},
                             {"upload_bytes": io_bytes}),
                         kernel_shapes=SHAPES, lanes=256,
                         device_kind="TPU v5 lite", n_devices=1)


def _busy_by_grid(ctx, step_ns=100):
    """Busy time on a 100 ns grid: an independent count of the union."""
    t0 = int(ctx.t0 * 1e9)
    n = int((ctx.t1 - ctx.t0) * 1e9) // step_ns + 1
    grid = np.zeros(n, bool)
    for _, _, s, e in ctx.ops:
        a = max(int(s * 1e9) - t0, 0) // step_ns
        b = min(int(e * 1e9) - t0, n * step_ns) // step_ns
        grid[a:b] = True
    return grid.sum() * step_ns * 1e-9


def test_busy_union_and_idle_share():
    ctx = _ctx()
    assert ctx.window_s == pytest.approx(0.502704276)
    assert ctx.busy_s == pytest.approx(_busy_by_grid(ctx), abs=2e-4)
    idle = spec.layer_reader("device.idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - ctx.busy_s / ctx.window_s))
    assert 5 < idle < 40


def test_per_round_readers():
    ctx = _ctx()
    rounds = [(s, e) for n, s, e in ctx.spans if n == "bench.round"]
    assert len(rounds) == 3
    adv = [(s, e) for n, s, e in ctx.spans if n == "bench.advance"]
    adv_s = sum(e - s for s, e in adv)
    busy_in = ctx.device_busy_in("bench.advance")
    assert 0 < busy_in <= adv_s
    assert spec.layer_reader("advance.host_ms")(ctx) == pytest.approx(
        (adv_s - busy_in) / 3 * 1e3)
    assert spec.layer_reader("device.busy_ms")(ctx) == pytest.approx(
        ctx.busy_s / 3 * 1e3)
    assert spec.layer_reader("pool.upload_kb")(ctx) == pytest.approx(1000.0)
    sub = sum(e - s for n, s, e in ctx.spans if n == "bench.submit")
    assert spec.layer_reader("frontdoor.submit_ms")(ctx) == pytest.approx(
        sub / 3 * 1e3)


def test_kernel_events_and_roofline_share():
    ctx = _ctx()
    evs = ctx.kernel_events("contention_pallas")
    assert len(evs) == 3                   # one call per round's tick
    flops, nbytes = roofline.WORK["contention"](*SHAPES["contention"])
    least = max(flops / 197e12, nbytes / 819e9) * 256
    want = 100 * least * 3 / sum(e - s for *_, s, e in evs)
    got = spec.layer_reader("contention_roofline")(ctx)
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_a_kernel_absent_from_the_trace_reads_nothing():
    ctx = _ctx()
    assert roofline.share(ctx, "contention", "no_such_kernel") is None
    ctx.kernel_shapes = {}
    assert spec.layer_reader("contention_roofline")(ctx) is None


def test_breakdown_lists_ops_and_labelled_gaps():
    b = _ctx().breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert any("maxmin_pallas" in n for n in names)
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert {g for g, _ in b["idle_gaps"]} <= {
        "bench.submit", "bench.advance", "bench.poll", "outside any call"}


def test_op_name_keeps_the_instruction_name():
    assert trace.op_name("%vmap_jit_maxmin_pallas__.11 = f32[256,1,2048]"
                         "{2,1,0} custom-call(f32[1] %a)") == \
        "vmap_jit_maxmin_pallas__.11"
