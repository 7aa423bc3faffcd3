"""Seeded coflow streams for the benchmark's cells.

One general generator reads two data files: the configuration's coflow
shapes (`configs/<name>.json`, key "coflows") and the traffic mix
(`traffic/<name>.json`). The per-coflow structure is a copy of the
FB-like synthesizer the program ships (`repro.traces.synth.
fb_like_trace`): 23% single-flow coflows, the rest M x R shuffles with
Pareto fan-outs, 65% of those with equal flows and the others
lognormal-skewed, a 1 KB per-flow floor that keeps the coflow total,
lognormal coflow totals. It is kept here so that later changes to the
program cannot change the benchmark's traffic.

Two departures from that synthesizer, both so that every seed offers
the same work:

- The stream does not end: the arrival rate is set, as the
  synthesizer sets it for one trace, from the sample mean of the first
  `RATE_COFLOWS` coflows (526, the trace's length), and the stream runs
  on past them at that rate.
- The coflows are drawn from one fixed population (`POPULATION_SEED`,
  in blocks of `BLOCK` coflows), and `--seed` only permutes the
  coflows and the inter-arrival gaps within consecutive groups of
  `SHUFFLE`. Every seed thus offers the same sizes and arrivals, in
  another order, and the backlog follows nearly the same course: with
  heavy-tailed sizes, a seed that moved the big coflows across the
  stream would change the work.

Arrivals are Poisson (exponential gaps) at the rate that gives the
traffic mix's `load`, the one parameter a mix sets.
"""
from __future__ import annotations

import math

import numpy as np

BLOCK = 64              # coflows drawn per block of the population
SHUFFLE = 8             # the seed permutes within groups of this many
RATE_COFLOWS = 526      # the rate sample: FB2010-1Hr-150-0's length
POPULATION_SEED = 0


def _floor_preserving_total(per: np.ndarray, total: float,
                            floor: float) -> np.ndarray:
    """Raise flows to `floor` bytes without changing the coflow total:
    floored flows are fixed and the rest renormalized, until none falls
    below; an infeasible floor splits the total equally."""
    per = np.asarray(per, float).copy()
    w = per.size
    if total <= floor * w:
        return np.full(w, total / w)
    fixed = np.zeros(w, bool)
    for _ in range(w):
        budget = total - floor * fixed.sum()
        free = ~fixed
        per[free] *= budget / per[free].sum()
        low = free & (per < floor)
        if not low.any():
            break
        fixed |= low
        per[fixed] = floor
    return per


class CoflowSpec:
    """One generated coflow: arrival (s), and per-flow src, dst, bytes."""
    __slots__ = ("cid", "arrival", "src", "dst", "size")

    def __init__(self, cid, arrival, src, dst, size):
        self.cid, self.arrival = cid, arrival
        self.src, self.dst, self.size = src, dst, size

    @property
    def total_bytes(self) -> float:
        return float(self.size.sum())


def _block(shape: dict, ports: int, tenant: int, b: int):
    """Block `b` of a tenant's population: per-coflow kind, total bytes,
    fan-outs, equal/skewed flag and the unit-rate exponential gaps,
    drawn as vectors (cheap: the arrival rate needs the totals only)."""
    n = BLOCK
    rng = np.random.default_rng([POPULATION_SEED, tenant, b])
    kind = rng.uniform(size=n)
    totals = np.clip(
        np.exp(rng.normal(math.log(shape["size_median_bytes"]),
                          shape["size_sigma"], n)),
        shape["size_min_bytes"], shape["size_max_bytes"])

    def fanout():
        x = 1 + rng.pareto(shape["fanout_pareto_shape"], n) \
            * shape["fanout_scale"]
        return np.minimum(np.ceil(x).astype(int), ports)

    M, R = fanout(), fanout()
    equal = rng.uniform(size=n) < shape["frac_equal_of_multi"]
    gaps = rng.exponential(1.0, n)
    return kind, totals, M, R, equal, gaps


def _flows(shape: dict, ports: int, rng, kind, total, m, r, equal):
    """One coflow's flows (src, dst, bytes), as the program's
    synthesizer builds them: a single flow, or an M x R shuffle with its
    width capped by halving the larger side, equal or lognormal-skewed
    flow sizes, and the total-preserving per-flow floor."""
    if kind < shape["frac_single"]:
        s, d = rng.choice(ports, 2, replace=False)
        return np.array([s]), np.array([d]), np.array([total])
    while m * r > shape["max_width"]:
        if m >= r:
            m = max(1, m // 2)
        else:
            r = max(1, r // 2)
    senders = rng.choice(ports, m, replace=False)
    receivers = rng.choice(ports, r, replace=False)
    w = m * r
    if equal:
        per = np.full(w, total / w)
    else:
        skew = np.exp(rng.normal(0.0, 1.0, w))
        per = total * skew / skew.sum()
    per = _floor_preserving_total(per, total, shape["flow_floor_bytes"])
    return np.repeat(senders, r), np.tile(receivers, m), per


class TenantStream:
    """The arrivals of one tenant, generated block by block on demand
    (the same seed gives the same stream however far it is read).

    The arrival rate is the one the program's synthesizer would give a
    trace of the tenant's first `RATE_COFLOWS` coflows: offered bytes
    over the trace's span are `load` of the fabric's capacity."""

    def __init__(self, shape: dict, ports: int, port_bw: float,
                 load: float, tenant: int, seed: int):
        self.shape = shape
        self.ports = ports
        self.tenant = tenant
        nb = -(-RATE_COFLOWS // BLOCK)
        first = np.concatenate([_block(shape, ports, tenant, b)[1]
                                for b in range(nb)])[:RATE_COFLOWS]
        self.rate = load * ports * port_bw / float(first.mean())
        self.seed = int(seed)
        self._b = 0
        self._t = 0.0            # arrival time of the last coflow made
        self._queue: list = []
        self._head = 0

    def _more(self) -> None:
        b = self._b
        kind, totals, M, R, equal, gaps = _block(
            self.shape, self.ports, self.tenant, b)
        # the seed permutes coflows and gaps within groups of SHUFFLE
        perm = np.random.default_rng([self.seed, self.tenant, b])
        n = len(totals)

        def shuffled():
            return np.concatenate([
                k + perm.permutation(min(SHUFFLE, n - k))
                for k in range(0, n, SHUFFLE)])

        order = shuffled()
        gaps = gaps[shuffled()]
        for j, k in enumerate(order):
            self._t += gaps[j] / self.rate
            rng = np.random.default_rng(
                [POPULATION_SEED, self.tenant, b, int(k)])
            src, dst, size = _flows(self.shape, self.ports, rng, kind[k],
                                    float(totals[k]), int(M[k]),
                                    int(R[k]), bool(equal[k]))
            self._queue.append(CoflowSpec(b * n + j, self._t, src, dst,
                                          size))
        self._b += 1

    def until(self, t: float) -> list:
        """The not yet taken coflows that arrive at or before `t`."""
        q = self._queue
        while self._head >= len(q) or q[-1].arrival <= t:
            self._more()
        out = []
        while q[self._head].arrival <= t:
            out.append(q[self._head])
            self._head += 1
        if self._head > 4096:
            del q[:self._head]
            self._head = 0
        return out


def streams(config: dict, traffic: dict, seed: int) -> list:
    """One `TenantStream` per tenant of the configuration, each at the
    mix's load."""
    return [TenantStream(config["coflows"], config["num_ports"],
                         config["params"]["port_bw"],
                         float(traffic["load"]), i, seed)
            for i in range(config["tenants"])]
