"""From a profiler trace of the window to the per-layer numbers.

The window of a `--trace 1` run is recorded by `jax.profiler` (Python
tracer off). `Context.load` reads the `.xplane.pb` with
`jax.profiler.ProfileData` and keeps two kinds of intervals on the
profiler's one clock:

- device operations: the events of each TPU plane's "XLA Ops" line
  (every op, the ops inside the session while_loop included; the
  Pallas kernels appear as `vmap_jit_<kernel>_pallas__.<n>`);
- host spans: the harness's own `bench.*` annotations around the calls
  into each layer (`bench.round`, `bench.submit`, `bench.advance`,
  `bench.poll`).

The readers in `bench/layers/` take their numbers from a `Context`.
"""
from __future__ import annotations

import glob
from pathlib import Path

OPS_LINE = "XLA Ops"


def op_name(hlo: str) -> str:
    """An XLA op event's name as the trace gives it is the HLO text
    (`%vmap_jit_contention_pallas__.11 = f32[...] custom-call(...)`);
    keep the instruction's own name."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def overlap(merged, s: float, e: float) -> float:
    """Length of [s, e) covered by disjoint sorted intervals."""
    import bisect

    starts = [m[0] for m in merged]
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    tot = 0.0
    while i < len(merged) and merged[i][0] < e:
        a, b = max(merged[i][0], s), min(merged[i][1], e)
        if b > a:
            tot += b - a
        i += 1
    return tot


class Context:
    """What a layer reader may read. Times are in seconds."""

    def __init__(self, ops, spans, rounds, io, kernel_shapes, lanes,
                 device_kind, n_devices):
        self.ops = ops            # [(device, name, start, end)]
        self.spans = spans        # [(name, start, end)]
        self.rounds = rounds
        self.io0, self.io1 = io
        self.kernel_shapes = kernel_shapes   # op -> per-lane shape
        self.lanes = lanes
        self.device_kind = device_kind
        self.n_devices = n_devices
        win = [(s, e) for n, s, e in spans if n == "bench.round"]
        self.t0 = min(s for s, _ in win) if win else 0.0
        self.t1 = max(e for _, e in win) if win else 0.0
        self.window_s = self.t1 - self.t0
        per_dev: dict = {}
        for d, _, s, e in ops:
            if s < self.t1 and e > self.t0:
                per_dev.setdefault(d, []).append(
                    (max(s, self.t0), min(e, self.t1)))
        self.busy = {d: union(v) for d, v in per_dev.items()}
        self.busy_s = (sum(length(m) for m in self.busy.values())
                       / max(n_devices, 1))

    @classmethod
    def load(cls, trace_dir, **kw) -> "Context":
        from jax.profiler import ProfileData

        files = sorted(glob.glob(str(Path(trace_dir) / "**" /
                                     "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        ops, spans = [], []
        for f in files:
            pd = ProfileData.from_file(f)
            for plane in pd.planes:
                dev = plane.name.startswith("/device:TPU:")
                for line in plane.lines:
                    if dev and line.name == OPS_LINE:
                        for ev in line.events:
                            ops.append((plane.name, op_name(ev.name),
                                        ev.start_ns * 1e-9,
                                        ev.end_ns * 1e-9))
                    elif not dev:
                        for ev in line.events:
                            if ev.name.startswith("bench."):
                                spans.append((ev.name, ev.start_ns * 1e-9,
                                              ev.end_ns * 1e-9))
        return cls(ops, spans, **kw)

    def span_total(self, name: str) -> float:
        return float(sum(e - s for n, s, e in self.spans if n == name))

    def device_busy_in(self, name: str) -> float:
        """Seconds of the spans named `name` in which some device op ran
        (averaged over devices)."""
        tot = 0.0
        for n, s, e in self.spans:
            if n == name:
                tot += sum(overlap(m, s, e) for m in self.busy.values())
        return tot / max(self.n_devices, 1)

    def kernel_events(self, match: str) -> list:
        """Device op events whose name contains `match`, in the window."""
        return [(d, n, s, e) for d, n, s, e in self.ops
                if match in n and s >= self.t0 and e <= self.t1]

    def breakdown(self) -> dict:
        """The ten device ops that took most time, and the ten longest
        idle gaps of device 0 labelled by the host span they fall in."""
        tot: dict = {}
        for _, n, s, e in self.ops:
            if s >= self.t0 and e <= self.t1:
                tot[n] = tot.get(n, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
        gaps = []
        if self.busy:
            m = self.busy[sorted(self.busy)[0]]
            edges = [self.t0] + [x for iv in m for x in iv] + [self.t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((a, b))
        inner = [(n, s, e) for n, s, e in self.spans if n != "bench.round"]
        labelled = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
            best, cover = "outside any call", 0.0
            for n, s, e in inner:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = n, c
            labelled.append([best, b - a])
        return {"device_ops": [[n, v] for n, v in top],
                "idle_gaps": labelled}
