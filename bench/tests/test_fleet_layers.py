"""The readers a max-min leaf-spine fleet adds (`maxmin_roofline`,
`tick.maxmin_ms`, `pool.scatter_rows`), on a small synthetic trace of
two rounds on two chips (an XSpace written from its text form): each
number against one worked out by hand, and nothing read from the trace
of a program without the scope or the counter."""
from pathlib import Path

import pytest

from bench import program, roofline, spec, trace

MS = 1_000_000          # ns
NEW = ["maxmin_roofline", "tick.maxmin_ms", "pool.scatter_rows"]
HARNESS = [("bench.round", 1.0, 11.0), ("bench.round", 11.0, 21.0)]
S = "pmap(block)/saath.session/while/body"
MM = f"{S}/vmap(saath.tick.maxmin)/jit(maxmin_pallas)/pallas_call"
# (device, HLO instruction, op_name path, start ms, end ms)
OPS = [
    (0, "fusion.1", f"{S}/vmap(saath.tick.maxmin)/eq", 2.0, 2.5),
    (0, "vmap_jit_maxmin_pallas__.3", MM, 2.5, 4.5),
    (0, "fusion.2", f"{S}/vmap(saath.tick.horizon)/select_n", 4.5, 5.0),
    (0, "vmap_jit_maxmin_pallas__.3", MM, 12.0, 13.0),
    (1, "vmap_jit_maxmin_pallas__.3", MM, 3.0, 6.0),
    (1, "fusion.1", f"{S}/vmap(saath.tick.maxmin)/eq", 5.5, 6.5),
    (1, "vmap_jit_maxmin_pallas__.3", MM, 14.0, 15.0),
]
# the max-min fill as a program without its own scope runs it
WC = "saath.tick.wc_fill"
IO = ({"upload_bytes": 0, "scatter_rows": 40},
      {"upload_bytes": 5000, "scatter_rows": 104})
SHAPE = (30, 1024)          # ports and leaf links, flows
LANES = 8                   # 4 rows a chip


def _xspace(scoped: bool) -> bytes:
    from jax.profiler import ProfileData

    names = {n: i + 1 for i, n in enumerate(dict.fromkeys(
        o[1] for o in OPS))}
    path = {o[1]: o[2] if scoped else o[2].replace("saath.tick.maxmin", WC)
            for o in OPS}
    planes = ""
    for dev in (0, 1):
        events = "".join(
            f"events {{ metadata_id: {names[n]} offset_ps: "
            f"{int(s * MS) * 1000} duration_ps: {int((e - s) * MS) * 1000}"
            f" }}\n" for d, n, _, s, e in OPS if d == dev)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: '
            f'"%{n} = f32[] op()" stats {{ metadata_id: 1 str_value: '
            f'"{path[n]}" }} }} }}\n' for n, i in names.items())
        planes += f"""
planes {{ id: {dev + 1} name: "/device:TPU:{dev}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{events} }}
{meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}"""
    host = "".join(
        f"events {{ metadata_id: 1 offset_ps: {int(s * MS) * 1000} "
        f"duration_ps: {int((e - s) * MS) * 1000} }}\n"
        for _, s, e in HARNESS)
    planes += f"""
planes {{ id: 9 name: "/host:CPU"
  lines {{ id: 2 name: "python" timestamp_ns: 0
{host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.round" }} }}
}}"""
    return ProfileData.text_proto_to_serialized_xspace(planes)


def _ctx(tmp_path: Path, monkeypatch, scoped: bool = True, io=IO):
    d = tmp_path / ("scoped" if scoped else "plain")
    f = d / "plugins" / "profile" / "run" / "host.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(_xspace(scoped))
    monkeypatch.setattr(program, "TRACE_DIR", d)
    return trace.Context.load(d, rounds=2, io=io,
                              kernel_shapes={"maxmin": SHAPE},
                              lanes=LANES, device_kind="TPU v5 lite",
                              n_devices=2)


def _read(name, ctx):
    return spec.layer_reader(name)(ctx)


def test_maxmin_roofline_counts_each_chips_rows(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch)
    pk = roofline.peaks("TPU v5 lite")
    flops, nbytes = roofline.maxmin_work(*SHAPE)
    least = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    kernel_s = (2.0 + 1.0 + 3.0 + 1.0) * 1e-3
    want = 100.0 * least * (LANES / 2) * 4 / kernel_s
    assert _read("maxmin_roofline", ctx) == pytest.approx(want)


def test_maxmin_scope_per_round_averaged_over_chips(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch)
    # chip 0: [2.0, 4.5] and [12, 13]; chip 1: [3.0, 6.5] and [14, 15]
    busy = ((2.5 + 1.0) + (3.5 + 1.0)) / 2
    assert _read("tick.maxmin_ms", ctx) == pytest.approx(busy / 2)


def test_scatter_rows_per_round(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch)
    assert _read("pool.scatter_rows", ctx) == pytest.approx(64 / 2)


def test_a_program_without_the_scope_or_counter_reads_nothing(
        tmp_path, monkeypatch):
    io = ({"upload_bytes": 0}, {"upload_bytes": 5000})
    ctx = _ctx(tmp_path, monkeypatch, scoped=False, io=io)
    assert _read("tick.maxmin_ms", ctx) is None
    assert _read("pool.scatter_rows", ctx) is None
    # the kernel is named by itself, whatever scope it ran under
    assert _read("maxmin_roofline", ctx) is not None
