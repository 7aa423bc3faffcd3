#!/usr/bin/env python3
"""Where the traced rounds of a `--trace 1` run went, by the program's
own spans and scopes (`bench/program.py`); prints one JSON object.

    python bench/breakdown.py [trace_dir]     # default bench/.trace

- `round_ms_p50`: the traced rounds' median latency (what tracing
  costs, beside an untraced run's p50);
- `device_ops`: the ops that took most device time, each as
  `name (scope)`;
- `scopes`: device seconds under each `saath.` scope, and the share of
  busy time under a `saath.tick.*` scope (the session loop's own event,
  which encloses every tick, left out of both sides);
- `idle_gaps`: the longest idle gaps of the first device, each labelled
  by the innermost span, harness or program, that covers its middle,
  with the program spans it overlaps;
- `idle_in_advance`: idle seconds inside `bench.advance` and the part of
  them a `saath.*` span covers;
- `dispatches`: for each `saath.pool.dispatch`, whether it starts
  before the session loop it enqueued and the `saath.pool.sync_ctl` of
  the same dispatch ends after it (the clocks agree).
"""
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import program, trace  # noqa: E402

SESSION = "saath.session"
TICK = "saath.tick."


def _window(trace_dir):
    """(t0, t1, rounds, harness spans) of the trace's bench.round spans."""
    from jax.profiler import ProfileData

    spans = []
    for f in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        for plane in ProfileData.from_file(str(f)).planes:
            if not plane.name.startswith(program.DEVICE_PREFIX):
                spans += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                          for line in plane.lines for ev in line.events
                          if ev.name.startswith("bench.")]
    rounds = [(s, e) for n, s, e in spans if n == "bench.round"]
    if not rounds:
        raise SystemExit(f"no bench.round span in a trace under {trace_dir}")
    return min(s for s, _ in rounds), max(e for _, e in rounds), \
        len(rounds), spans


def _is_loop(op) -> bool:
    return op[2] == SESSION and op[1].startswith("while")


def breakdown(p: "program.Program", bench_spans, top: int = 12) -> dict:
    dev0 = sorted({op[0] for op in p.ops})[0] if p.ops else None
    ops = [op for op in p.ops if op[0] == dev0]
    tot: dict = {}
    for _, name, scope, s, e in ops:
        key = f"{name} ({scope})"
        tot[key] = tot.get(key, 0.0) + min(e, p.t1) - max(s, p.t0)
    clip = [(max(s, p.t0), min(e, p.t1)) for *_, s, e in ops]
    busy = trace.union(clip)
    inner = trace.union([iv for op, iv in zip(ops, clip)
                         if not _is_loop(op)])
    ticked = trace.union([iv for op, iv in zip(ops, clip)
                          if (op[2] or "").startswith(TICK)])
    scopes: dict = {}
    for op, iv in zip(ops, clip):
        scopes.setdefault(op[2], []).append(iv)

    spans = [(n, s, e) for n, s, e in bench_spans if n != "bench.round"] \
        + [(n, s, e) for n, s, e, _ in p.spans]
    edges = [p.t0] + [x for iv in busy for x in iv] + [p.t1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (a + b) / 2
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else "outside any call"
        parts = sorted(((n, min(b, e) - max(a, s)) for n, s, e, _ in p.spans
                        if min(b, e) > max(a, s)), key=lambda x: -x[1])
        labelled.append([label, b - a, parts[:4]])

    adv = trace.union([(s, e) for n, s, e in bench_spans
                       if n == "bench.advance"])
    prog = trace.union([(s, e) for _, s, e, _ in p.spans])
    idle_adv = idle_adv_prog = 0.0
    for a, b in gaps:
        for s, e in adv:
            lo, hi = max(a, s), min(b, e)
            if hi > lo:
                idle_adv += hi - lo
                idle_adv_prog += trace.overlap(prog, lo, hi)

    loops = sorted((op for op in ops if _is_loop(op)), key=lambda op: op[3])
    disp = sorted((sp for sp in p.spans if sp[0] == "saath.pool.dispatch"),
                  key=lambda sp: sp[1])
    sync = {sp[3].get("dispatch"): sp for sp in p.spans
            if sp[0] == "saath.pool.sync_ctl"}
    checks = []
    for d, loop in zip(disp, loops):
        n = d[3].get("dispatch")
        w = sync.get(n)
        checks.append([n, d[1] <= loop[3], w is not None and w[2] >= loop[4]])
    return {
        "device_ops": sorted(([k, v] for k, v in tot.items()),
                             key=lambda kv: -kv[1])[:top],
        "scopes": {str(k): trace.length(trace.union(v))
                   for k, v in sorted(scopes.items(), key=str)},
        "busy_s": trace.length(busy),
        "tick_scoped_share": (trace.length(ticked) / trace.length(inner)
                              if inner else None),
        "idle_gaps": labelled,
        "idle_in_advance": [idle_adv, idle_adv_prog],
        "dispatches": checks,
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    trace_dir = Path(args[0]) if args else program.TRACE_DIR
    t0, t1, rounds, bench_spans = _window(trace_dir)
    p = program.Program.load(trace_dir, t0, t1, n_devices=1)
    lat = [e - s for n, s, e in bench_spans if n == "bench.round"]
    out = {"rounds": rounds, "window_s": t1 - t0,
           "round_ms_p50": statistics.median(lat) * 1e3}
    out.update(breakdown(p, bench_spans))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
