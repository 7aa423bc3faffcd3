"""The program's own spans and scopes in a `--trace 1` run's trace.

`bench/trace.py`'s `Context` keeps the harness's `bench.*` spans and
each device op by its HLO name. The program adds, on the profiler's
same clock:

- host spans: `jax.profiler.TraceAnnotation`s named `saath.server.*`
  (`repro.launch.serve`) and `saath.pool.*` (`repro.api.pool`), with
  their metadata (`dispatch=<n>` on `saath.pool.dispatch` and
  `saath.pool.sync_ctl`);
- device scopes: every op of the compiled tick carries the
  `jax.named_scope`s it was traced under in its `op_name`, which the
  TPU trace keeps as the `tf_op` stat of the op's event metadata
  (`.../saath.session/while/body/vmap(saath.tick.wc_fill)/while/...`),
  and for a `while` op only in its program's HLO. An op's scope is the
  innermost `saath.` component of that path.

`of(ctx)` reads them from the trace the context was loaded from (the
harness writes it under `bench/.trace`), once per context, and returns
None where that trace is not the context's. In the trace of a program
without spans (or scopes) every span (or scope) reads None; in that of
a program with them, one that did not run in the window reads 0.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

from bench import trace

TRACE_DIR = Path(__file__).resolve().parent / ".trace"
SCOPE = re.compile(r"saath\.[A-Za-z0-9_.]+")
DEVICE_PREFIX = "/device:TPU:"


def scope_of(op_name: str):
    """The innermost `saath.` scope of an op's `op_name` path, or None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


# ---- the few protobuf messages of an XSpace the scopes need ---------------
#
# `jax.profiler.ProfileData` gives each event its own stats but not the
# stats of its event metadata, where the TPU trace keeps `tf_op`; these
# read them from the file's protobuf wire format: XSpace.planes = 1;
# XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (both maps
# of key = 1 to value = 2); XEventMetadata.name = 2, .stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, .uint64_value = 3,
# .int64_value = 4, .str_value = 5, .bytes_value = 6, .ref_value = 7.
# The TPU trace gives no `tf_op` to control-flow ops (`while`); their
# `op_name` is read from the program's HLO, which the `/host:metadata`
# plane keeps per program as an "Hlo Proto" stat (HloProto.hlo_module =
# 1; HloModuleProto.computations = 3; HloComputationProto.instructions
# = 2; HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name
# = 2), its event metadata named `<module>(<program id>)`.

def _varint(buf, i: int):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            v, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode()


def _planes(data):
    """(name, [event metadata], {stat metadata id: name}) of each plane."""
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g in (4, 5):
                value = dict(_fields(v)).get(2, b"")
                if g == 4:
                    metas.append(value)
                else:
                    sm = dict(_fields(value))
                    stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        yield name, metas, stat_names


def _event_meta(meta, stat_names) -> tuple:
    """(name, {stat name: value}) of one XEventMetadata; a `ref_value`
    reads as the name of the stat metadata it refers to."""
    name, stats = "", {}
    for g, v in _fields(meta):
        if g == 2:
            name = _text(v)
        elif g == 5:
            st = dict(_fields(v))
            key = stat_names.get(st.get(1), "")
            if 7 in st:
                stats[key] = stat_names.get(st[7], "")
            elif 5 in st:
                stats[key] = _text(st[5])
            else:
                stats[key] = st.get(3, st.get(4, st.get(6)))
    return name, stats


def _hlo_op_names(hlo_proto) -> dict:
    """{instruction name: op_name} of one serialized HloProto."""
    out = {}
    module = dict(_fields(hlo_proto)).get(1, b"")
    for g, comp in _fields(module):
        if g != 3:
            continue
        for h, ins in _fields(comp):
            if h != 2:
                continue
            fields = dict(_fields(ins))
            meta = dict(_fields(fields.get(7, b"")))
            out[_text(fields.get(1, b""))] = _text(meta.get(2, b""))
    return out


def op_scopes(data: bytes) -> dict:
    """{HLO text of a device op: its scope} from a serialized XSpace:
    from the op's `tf_op` where the trace gives one, else from the
    `op_name` of its instruction in its program's HLO. Ops whose path
    names no `saath.` scope map to None."""
    hlo, ops = {}, []
    for name, metas, stat_names in _planes(data):
        if name == "/host:metadata":
            for meta in metas:
                mname, stats = _event_meta(meta, stat_names)
                if "Hlo Proto" in stats and mname.endswith(")"):
                    pid = mname[mname.rindex("(") + 1:-1]
                    hlo[pid] = stats["Hlo Proto"]
        elif name.startswith(DEVICE_PREFIX):
            ops += [_event_meta(meta, stat_names) for meta in metas]
    parsed: dict = {}
    out: dict = {}
    for text, stats in ops:
        path = stats.get("tf_op")
        pid = str(stats.get("program_id"))
        if not path and pid in hlo:
            if pid not in parsed:
                parsed[pid] = _hlo_op_names(hlo[pid])
            path = parsed[pid].get(trace.op_name(text))
        if out.get(text) is None:
            out[text] = scope_of(path)
    return out


class Program:
    """The program's host spans and scoped device ops of one trace,
    clipped to the traced window [t0, t1]. Times are in seconds."""

    def __init__(self, spans, ops, t0: float, t1: float, n_devices: int):
        self.t0, self.t1 = t0, t1
        self.n_devices = n_devices
        # [(name, start, end, {metadata})]
        self.spans = [sp for sp in spans if sp[1] >= t0 and sp[2] <= t1]
        # [(device, name, scope, start, end)]
        self.ops = [op for op in ops if op[3] < t1 and op[4] > t0]

    @classmethod
    def load(cls, trace_dir, t0: float, t1: float,
             n_devices: int) -> "Program | None":
        """Read the `saath.*` spans and the scoped device ops of the
        trace under `trace_dir`; None where it holds none or its
        `bench.round` window is not [t0, t1] (another run's trace)."""
        from jax.profiler import ProfileData

        files = sorted(glob.glob(str(Path(trace_dir) / "**" /
                                     "*.xplane.pb"), recursive=True))
        if not files:
            return None
        spans, ops, rounds = [], [], []
        for path in files:
            data = Path(path).read_bytes()
            scopes = op_scopes(data)
            pd = ProfileData.from_serialized_xspace(data)
            for plane in pd.planes:
                dev = plane.name.startswith(DEVICE_PREFIX)
                for line in plane.lines:
                    if dev and line.name == trace.OPS_LINE:
                        for ev in line.events:
                            ops.append((plane.name, trace.op_name(ev.name),
                                        scopes.get(ev.name),
                                        ev.start_ns * 1e-9,
                                        ev.end_ns * 1e-9))
                    elif not dev:
                        for ev in line.events:
                            iv = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                            if ev.name.startswith("saath."):
                                spans.append((ev.name, *iv,
                                              dict(ev.stats)))
                            elif ev.name == "bench.round":
                                rounds.append(iv)
        if not rounds or abs(min(s for s, _ in rounds) - t0) > 1e-6 \
                or abs(max(e for _, e in rounds) - t1) > 1e-6:
            return None
        return cls(spans, ops, t0, t1, n_devices)

    def span_total(self, name: str):
        """Seconds covered by the spans named `name` (the union, so a
        span nested in another of its name counts once); None where the
        trace holds no program span at all."""
        if not self.spans:
            return None
        return trace.length(trace.union(
            [(s, e) for n, s, e, _ in self.spans if n == name]))

    def scope_busy(self, name: str):
        """Device seconds in which an op of scope `name` ran (the union
        of its ops' intervals, averaged over devices); None where no op
        of the trace has a scope."""
        if not any(op[2] for op in self.ops):
            return None
        per_dev: dict = {}
        for d, _, sc, s, e in self.ops:
            if sc == name:
                per_dev.setdefault(d, []).append(
                    (max(s, self.t0), min(e, self.t1)))
        return (sum(trace.length(trace.union(v)) for v in per_dev.values())
                / max(self.n_devices, 1))


def of(ctx):
    """The `Program` of the trace `ctx` was read from, loaded once per
    context (kept as `ctx.program`); None where there is none."""
    if not hasattr(ctx, "program"):
        ctx.program = Program.load(TRACE_DIR, ctx.t0, ctx.t1,
                                   ctx.n_devices) if ctx.rounds else None
    return ctx.program


def per_round_ms(ctx, seconds):
    """Seconds over the traced rounds, in ms a round; None passes."""
    if seconds is None or not ctx.rounds:
        return None
    return seconds / ctx.rounds * 1e3
