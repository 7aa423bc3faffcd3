"""The system under test, driven the way a coordinator's client drives it.

`Fleet` builds one `repro.launch.serve.CoflowServer` for a configuration
(one row per tenant of one shared slab, its rows sharded over the
cell's chips; a big switch, or the configuration's `topology`, e.g.
`{"kind": "leaf_spine", "hosts_per_leaf": 4, "oversub": 4.0,
"wc_fill": "maxmin"}`) and moves it in rounds: each
round submits every tenant's coflows that arrive within the next δ of
virtual time, calls `advance(δ)` (which returns once the pool's control
download is on the host) and polls every tenant's completions. Rounds
run back to back, and arrivals follow the stream's own virtual clock,
so the work per round does not depend on how fast the system is.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import gen


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def topology(config: dict):
    """The program's fabric model for the configuration's `topology`
    entry; None (a big switch) where it has none."""
    spec = config.get("topology")
    if spec is None:
        return None
    if spec["kind"] != "leaf_spine":
        raise ValueError(f"unknown topology kind {spec['kind']!r}")
    from repro.fabric.topology import LeafSpine

    return LeafSpine(hosts_per_leaf=int(spec["hosts_per_leaf"]),
                     oversub=float(spec["oversub"]),
                     wc_fill=spec["wc_fill"])


class Fleet:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 shards: int = 1):
        from repro.core.params import SchedulerParams
        from repro.launch.serve import CoflowServer

        self.config = config
        self.params = SchedulerParams(**config["params"])
        self.delta = self.params.delta
        self.n = int(config["tenants"])
        if self.n % shards:
            raise ValueError(
                f"{self.n} tenants cannot be sharded evenly over {shards} "
                f"chips: make the configuration's tenants a multiple of "
                f"the cell's chips")
        # a one-chip big switch gets exactly the arguments it always had
        extra = {}
        if shards > 1:
            extra["shards"] = shards
        if "topology" in config:
            extra["topology"] = topology(config)
        self.srv = CoflowServer(self.params, num_ports=config["num_ports"],
                                max_tenants=self.n, **extra)
        self.names = [f"tenant{i}" for i in range(self.n)]
        for name in self.names:
            self.srv.register(name)
        self.streams = gen.streams(config, traffic, seed)
        self.submitted = [[] for _ in range(self.n)]
        self._cid_of = [{} for _ in range(self.n)]
        # (tenant, cid) -> (cct, phase) of every harvested completion
        self.done: dict = {}
        self.now = 0.0
        self.refused = 0
        self.annotate = contextlib.nullcontext  # set by a traced run

    def _coflows(self, specs):
        from repro.core.coflow import Coflow, Flow

        return [Coflow(cid=s.cid, arrival=s.arrival,
                       flows=[Flow(j, int(a), int(b), float(z))
                              for j, (a, b, z) in enumerate(
                                  zip(s.src, s.dst, s.size))])
                for s in specs]

    def due(self, t: float) -> list:
        """(tenant, program coflows, specs) for arrivals up to `t`."""
        out = []
        for i, st in enumerate(self.streams):
            specs = st.until(t)
            if specs:
                out.append((i, self._coflows(specs), specs))
        return out

    def submit(self, batch) -> None:
        for i, cfs, specs in batch:
            handles = self.srv.submit(self.names[i], cfs)
            self.refused += len(cfs) - len(handles)
            for h, s in zip(handles, specs):
                self._cid_of[i][h] = s.cid
            self.submitted[i].extend(specs)

    def harvest(self, phase: str) -> None:
        for i, name in enumerate(self.names):
            for d in self.srv.poll(name):
                cid = self._cid_of[i].pop(d.handle)
                self.done[i, cid] = (float(d.cct), phase)

    def fast_forward(self, steps) -> None:
        """Submit and schedule the stream in advances of `steps` seconds
        (set-up: fills the backlog; each advance's arrivals are submitted
        before it, so the slab grows to hold them too)."""
        for dt in steps:
            self.submit(self.due(self.now + dt))
            self.srv.advance(dt)
            self.now += dt
            self.harvest("setup")

    def round(self, phase: str) -> float:
        """One coordinator round; returns its latency in seconds, from
        the first submit to the completions polled."""
        batch = self.due(self.now + self.delta)
        ann = self.annotate
        t0 = time.perf_counter()
        with ann("bench.submit"):
            self.submit(batch)
        with ann("bench.advance"):
            self.srv.advance(self.delta)
        with ann("bench.poll"):
            self.harvest(phase)
        t1 = time.perf_counter()
        self.now += self.delta
        return t1 - t0

    def io(self) -> dict:
        return dict(self.srv.pool.io)

    def slab(self) -> tuple:
        return tuple(self.srv.stats()["slab"])

    def live(self) -> int:
        return int(self.srv.stats()["live_coflows"])

    def served(self, phase: str) -> dict:
        """{(tenant, cid): cct} of the completions harvested in `phase`."""
        return {k: v[0] for k, v in self.done.items() if v[1] == phase}


def offered_load(fleet: Fleet, horizon: float) -> float:
    """Bytes submitted per second over the fabric's capacity, the mean
    over tenants (the realized load of the streams up to `horizon`)."""
    cap = (fleet.n * fleet.config["num_ports"] * fleet.params.port_bw
           * horizon)
    total = sum(s.total_bytes for sub in fleet.submitted for s in sub)
    return total / cap if cap else float("nan")


def percentile(values, q: float) -> float:
    """The q-th percentile with linear interpolation between order
    statistics (numpy's default), in plain Python."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
