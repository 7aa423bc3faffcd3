"""The readers of the program's own spans and scopes, on a small
synthetic trace of two rounds (an XSpace written from its text form):
each number against one worked out by hand from the timeline below, the
harness's readers unchanged by what the program adds, and nothing read
from a trace without it."""
from pathlib import Path

import pytest

from bench import breakdown, program, spec, trace

MS = 1_000_000          # ns
NEW = ["pool.stage_ms", "pool.sync_wait_ms", "server.harvest_ms",
       "tick.wc_fill_ms", "tick.wc_ns_per_trip"]
OLD = ["frontdoor.submit_ms", "pool.upload_kb", "advance.host_ms",
       "device.idle_share", "device.busy_ms", "contention_roofline"]

# (name, start ms, end ms, dispatch) of the host spans
HARNESS = [
    ("bench.round", 1.0, 11.0), ("bench.submit", 1.0, 1.5),
    ("bench.advance", 1.5, 10.0), ("bench.poll", 10.0, 11.0),
    ("bench.round", 11.0, 21.0), ("bench.submit", 11.0, 11.5),
    ("bench.advance", 11.5, 20.0), ("bench.poll", 20.0, 21.0),
]
PROGRAM = [
    ("saath.server.advance", 1.6, 9.9, None),
    ("saath.pool.stage", 2.0, 3.0, None),
    ("saath.pool.upload", 3.0, 3.2, None),
    ("saath.pool.dispatch", 3.2, 3.4, 1),
    ("saath.pool.sync_ctl", 3.4, 9.0, 1),
    ("saath.server.harvest", 9.0, 9.8, None),
    ("saath.server.harvest", 10.2, 10.4, None),
    ("saath.server.advance", 11.6, 19.9, None),
    ("saath.pool.dispatch", 11.7, 11.9, 2),
    ("saath.pool.sync_ctl", 11.9, 19.0, 2),
    ("saath.server.harvest", 19.0, 19.4, None),
    ("saath.server.harvest", 20.2, 20.3, None),
]
# (HLO instruction, op_name path, start ms, end ms) on /device:TPU:0
S = "jit(_run_session_block)/saath.session/while/body"
OPS = [
    ("while.1", "jit(_run_session_block)/saath.session/while", 3.5, 8.5),
    ("fusion.1", f"{S}/vmap(saath.tick.views)/add", 3.5, 4.0),
    ("contention_pallas.3",
     f"{S}/vmap(saath.tick.contention)/jit(contention_pallas)/pallas_call",
     4.0, 4.1),
    ("while.2", f"{S}/vmap(saath.tick.wc_fill)/while", 4.2, 8.0),
    ("dynamic-slice.4",
     f"{S}/vmap(saath.tick.wc_fill)/while/body/dynamic_slice", 4.3, 4.5),
    ("fusion.5", f"{S}/vmap(saath.tick.horizon)/select_n", 8.0, 8.4),
    ("add.6", f"{S}/add", 8.4, 8.5),
    ("copy.7", "jit(gather_rows)/gather", 9.2, 9.3),
    ("while.1", "jit(_run_session_block)/saath.session/while", 12.0, 18.0),
    ("fusion.1", f"{S}/vmap(saath.tick.views)/add", 12.0, 12.5),
    ("while.2", f"{S}/vmap(saath.tick.wc_fill)/while", 12.5, 17.5),
    ("fusion.5", f"{S}/vmap(saath.tick.horizon)/select_n", 17.5, 18.0),
]
IO = ({"upload_bytes": 0, "wc_trips": 100},
      {"upload_bytes": 5000, "wc_trips": 980})


def _proto(*fields) -> bytes:
    """A protobuf message of length-delimited (field, bytes) pairs."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    return b"".join(varint(f << 3 | 2) + varint(len(v)) + v
                    for f, v in fields)


def _hlo_proto(with_program: bool) -> str:
    """The session program's HloProto, holding the `op_name` of each
    `while` op (the TPU trace gives control-flow ops no `tf_op`), as a
    text-format bytes literal."""
    ins = [_proto((1, n.encode()),
                  (7, _proto((2, (p if with_program else "jit(f)/while")
                              .encode()))))
           for n, p, *_ in OPS[:4] if n.startswith("while")]
    data = _proto((1, _proto((3, _proto(*[(2, i) for i in ins])))))
    return "".join(f"\\{b:03o}" for b in data)


def _xspace(with_program: bool) -> bytes:
    """The timeline as a serialized XSpace; without the program's part
    it is what the parent commit's trace holds: no `saath.*` span and
    no `op_name` naming a scope."""
    from jax.profiler import ProfileData

    hlo = {n: i + 1 for i, n in enumerate(dict.fromkeys(n for n, *_ in OPS))}
    dev_events = "".join(
        f"events {{ metadata_id: {hlo[n]} offset_ps: {int(s * MS) * 1000} "
        f"duration_ps: {int((e - s) * MS) * 1000} }}\n"
        for n, _, s, e in OPS)
    path_of = {n: p for n, p, *_ in OPS}

    def stats(n):
        if n.startswith("while"):     # op_name from the program's HLO
            return "stats { metadata_id: 2 uint64_value: 77 } "
        return (f'stats {{ metadata_id: 1 str_value: "{path_of[n]}" }} '
                if with_program else "")
    dev_meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} '
        f'name: "%{n} = f32[] op()" ' + stats(n) + "} }\n"
        for n, i in hlo.items())
    host = [(n, s, e, None) for n, s, e in HARNESS] + \
        (PROGRAM if with_program else [])
    names = {n: i + 1 for i, n in enumerate(dict.fromkeys(h[0] for h in host))}
    host_events = "".join(
        f"events {{ metadata_id: {names[n]} offset_ps: {int(s * MS) * 1000} "
        f"duration_ps: {int((e - s) * MS) * 1000} "
        + (f"stats {{ metadata_id: 2 int64_value: {d} }} " if d else "")
        + "}\n" for n, s, e, d in host)
    host_meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}\n' for n, i in names.items())
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{dev_events} }}
{dev_meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "program_id" }} }}
}}
planes {{ id: 3 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1
    name: "jit__run_session_block(77)"
    stats {{ metadata_id: 1 bytes_value: "{_hlo_proto(with_program)}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 2 name: "python" timestamp_ns: 0
{host_events} }}
{host_meta}
  stat_metadata {{ key: 2 value {{ id: 2 name: "dispatch" }} }}
}}
"""
    return ProfileData.text_proto_to_serialized_xspace(text)


def _ctx(tmp_path: Path, with_program: bool = True):
    d = tmp_path / ("prog" if with_program else "plain")
    f = d / "plugins" / "profile" / "run" / "host.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(_xspace(with_program))
    ctx = trace.Context.load(d, rounds=2, io=IO,
                             kernel_shapes={"contention": (256, 150)},
                             lanes=1, device_kind="TPU v5 lite",
                             n_devices=1)
    return ctx, d


@pytest.fixture
def traced(tmp_path, monkeypatch):
    ctx, d = _ctx(tmp_path)
    monkeypatch.setattr(program, "TRACE_DIR", d)
    return ctx


def _read(name, ctx):
    return spec.layer_reader(name)(ctx)


def test_span_readers_per_round(traced):
    assert _read("pool.stage_ms", traced) == pytest.approx(1.0 / 2)
    assert _read("pool.sync_wait_ms", traced) == \
        pytest.approx((5.6 + 7.1) / 2)
    assert _read("server.harvest_ms", traced) == \
        pytest.approx((0.8 + 0.2 + 0.4 + 0.1) / 2)


def test_wc_fill_scope_and_time_per_trip(traced):
    # while.2 encloses its own body op: the union counts it once
    wc_s = ((8.0 - 4.2) + (17.5 - 12.5)) * 1e-3
    assert _read("tick.wc_fill_ms", traced) == pytest.approx(wc_s / 2 * 1e3)
    assert _read("tick.wc_ns_per_trip", traced) == \
        pytest.approx(wc_s / (980 - 100) * 1e9)


def test_scopes_from_tf_op_and_from_the_programs_hlo(traced):
    scopes = {trace.op_name(text): sc for text, sc in
              program.op_scopes(_xspace(True)).items()}
    assert scopes["while.1"] == "saath.session"      # from the HLO
    assert scopes["while.2"] == "saath.tick.wc_fill"
    assert scopes["dynamic-slice.4"] == "saath.tick.wc_fill"  # tf_op
    assert scopes["copy.7"] is None
    assert set(program.op_scopes(_xspace(False)).values()) == {None}


def test_scopes_from_the_op_name_path():
    assert program.scope_of(f"{S}/vmap(saath.tick.admit)/while/body/lt") \
        == "saath.tick.admit"
    assert program.scope_of("jit(gather_rows)/gather") is None
    assert program.scope_of(None) is None


def test_harness_readers_unchanged_by_the_program(tmp_path, monkeypatch):
    plain, d_plain = _ctx(tmp_path, with_program=False)
    monkeypatch.setattr(program, "TRACE_DIR", d_plain)
    before = {m: _read(m, plain) for m in OLD}
    ctx, d = _ctx(tmp_path)
    monkeypatch.setattr(program, "TRACE_DIR", d)
    for m in NEW:                      # the program part is read too
        assert _read(m, ctx) is not None
    assert {m: _read(m, ctx) for m in OLD} == before
    assert before["contention_roofline"] is not None
    assert ctx.breakdown() == plain.breakdown()


def test_a_trace_without_the_program_reads_nothing(tmp_path, monkeypatch):
    plain, d = _ctx(tmp_path, with_program=False)
    monkeypatch.setattr(program, "TRACE_DIR", d)
    assert {m: _read(m, plain) for m in NEW} == dict.fromkeys(NEW)


def test_another_runs_trace_reads_nothing(tmp_path, monkeypatch):
    ctx, _ = _ctx(tmp_path)
    monkeypatch.setattr(program, "TRACE_DIR", tmp_path / "nowhere")
    assert _read("pool.stage_ms", ctx) is None
    ctx2, d2 = _ctx(tmp_path / "b")
    ctx2.t0 += 1e-3                    # a window that is not the trace's
    monkeypatch.setattr(program, "TRACE_DIR", d2)
    assert _read("tick.wc_fill_ms", ctx2) is None


def test_a_span_that_did_not_run_reads_zero(traced):
    assert program.of(traced).span_total("saath.pool.gather") == 0.0
    assert program.of(traced).scope_busy("saath.tick.admit") == 0.0


def test_breakdown_labels_gaps_and_checks_the_clocks(traced):
    p = program.of(traced)
    b = breakdown.breakdown(p, traced.spans)
    assert b["dispatches"] == [[1, True, True], [2, True, True]]
    # busy outside the session loops' own events: 0.6 + 4.3 + 6.0 + 0.1
    # ms, of which saath.tick.* covers 0.6 + 4.2 + 6.0
    assert b["tick_scoped_share"] == pytest.approx(10.8 / 11.0)
    gaps = {round(g * 1e3, 6): label for label, g, _ in b["idle_gaps"]}
    assert gaps == {2.5: "saath.pool.stage", 0.7: "saath.pool.sync_ctl",
                    2.7: "bench.poll", 3.0: "saath.server.advance"}
    idle, covered = b["idle_in_advance"]
    assert idle == pytest.approx(5.9e-3)
    assert covered == pytest.approx(5.5e-3)
    assert b["device_ops"][0][0] == "while.1 (saath.session)"
