"""The plain reference: Saath's coordinator as a float64 numpy simulator.

A copy of the program's numpy plane (`repro.core.policies.saath`,
`repro.core.policies.base`, `repro.fabric.engine`,
`repro.fabric.topology`, `repro.core.queues`, `repro.core.contention`),
cut to what the benchmark's configurations state: clairvoyant Saath
with all-or-none admission, per-flow queue thresholds, LCoF order,
starvation deadlines, the §4.3 re-queue, and work conservation by the
paper's greedy per-flow walk. The fabric is a big switch, or a
leaf-spine (a configuration's `topology`): each leaf's uplink and
downlink carry the leaf's port bandwidth over `oversub`, an inter-leaf
flow crosses its source leaf's uplink and its destination leaf's
downlink, admission and the greedy walk check those links beside the
ports, and `wc_fill: "maxmin"` fills the leftover by max-min
water-filling instead of the walk. It imports nothing of the program,
so no later change to the program can move the yardstick.

The schedule is recomputed on the δ grid at events (an arrival, a flow
completion, a queue-threshold crossing, a deadline), rates are constant
in between, and flow completion instants are exact. `run(..., until=T)`
stops at the first tick at or past T.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

# a value within this relative band below a queue threshold counts as
# crossed, as in the program's both planes: the simulator lands flows
# exactly on thresholds, and precisions must not fork on the last ulp
CROSS_EPS = 1e-5


class Params:
    """The scheduler parameters a configuration states (paper §6)."""

    def __init__(self, delta, num_queues, start_threshold, growth,
                 deadline_factor, port_bw, min_rate_frac,
                 work_conservation=True, dynamics_requeue=True, **_):
        self.delta = float(delta)
        self.num_queues = int(num_queues)
        self.start_threshold = float(start_threshold)
        self.growth = float(growth)
        self.deadline_factor = float(deadline_factor)
        self.port_bw = float(port_bw)
        self.min_rate = self.port_bw * float(min_rate_frac)
        self.work_conservation = bool(work_conservation)
        self.dynamics_requeue = bool(dynamics_requeue)
        t = self.start_threshold * self.growth ** np.arange(self.num_queues)
        t[-1] = np.inf
        self._th = t

    def thresholds(self) -> np.ndarray:
        return self._th


class Links:
    """A leaf-spine fabric's leaf links: `cap[k]` is leaf k's uplink
    for k < Lf and leaf k - Lf's downlink above, `up[f]` and `dn[f]`
    flow f's uplink and downlink ids into `cap`, both -1 where the flow
    stays inside its leaf. `maxmin` selects the max-min fill."""

    def __init__(self, topology: dict, src, dst, bw):
        if topology["kind"] != "leaf_spine":
            raise ValueError(f"unknown topology kind {topology['kind']!r}")
        h = int(topology["hosts_per_leaf"])
        P = bw.shape[0]
        Lf = -(-P // h)
        leaf = np.arange(P) // h
        per_leaf = np.bincount(leaf, weights=bw, minlength=Lf) \
            / float(topology["oversub"])
        self.cap = np.concatenate([per_leaf, per_leaf])
        inter = src // h != dst // h
        self.up = np.where(inter, src // h, -1)
        self.dn = np.where(inter, dst // h + Lf, -1)
        self.maxmin = topology["wc_fill"] == "maxmin"


class Table:
    """All flows of the submitted coflows, flattened per coflow."""

    def __init__(self, coflows, num_ports: int, port_bw: float,
                 topology: Optional[dict] = None):
        C = len(coflows)
        self.num_ports = P = num_ports
        self.C = C
        w = np.array([len(c.size) for c in coflows], np.int64)
        self.flow_hi = np.cumsum(w)
        self.flow_lo = self.flow_hi - w
        self.width = w
        self.cid = np.repeat(np.arange(C), w)
        self.src = np.concatenate([c.src for c in coflows]).astype(np.int64)
        self.dst = np.concatenate([c.dst for c in coflows]).astype(np.int64)
        self.size = np.concatenate([c.size for c in coflows]).astype(
            np.float64)
        F = self.size.size
        self.sent = np.zeros(F)
        self.done = np.zeros(F, bool)
        self.fct = np.full(F, np.nan)
        self.arrival = np.array([c.arrival for c in coflows], np.float64)
        self.active = np.zeros(C, bool)
        self.finished = np.zeros(C, bool)
        self.cct = np.full(C, np.nan)
        self.bw = np.full(P, port_bw)
        self.links = None if topology is None else Links(
            topology, self.src, self.dst, self.bw)

    def flow_live(self):
        return self.active[self.cid] & ~self.done


def queue_of(value, p: Params) -> np.ndarray:
    q = np.searchsorted(p.thresholds(), np.asarray(value, np.float64)
                        * (1.0 + CROSS_EPS), side="right")
    return np.clip(q, 0, p.num_queues - 1)


def min_queue_residence(queue, width, p: Params) -> np.ndarray:
    th = p.thresholds()
    lo = np.concatenate([[0.0], th[:-1]])
    hi = th.copy()
    hi[-1] = lo[-1] * p.growth if p.num_queues > 1 else p.start_threshold
    return (hi - lo)[queue] / (np.maximum(width, 1) * p.port_bw)


def contention(A_s, A_r, active) -> np.ndarray:
    a_s = (A_s & active[:, None]).astype(np.float64)
    a_r = (A_r & active[:, None]).astype(np.float64)
    blocks = (a_s @ a_s.T + a_r @ a_r.T) > 0.5
    k = blocks.sum(axis=1) - blocks.diagonal()
    return np.where(active, k, 0)


def greedy_fill(t: Table, order, live, avail_s, avail_r, rates,
                avail_x=None) -> None:
    """The paper's D4 walk: each flow in order takes the least residual
    of its two ports, as a round-based walk in which each round
    allocates every flow that is the first in order on both its ports
    (the one-at-a-time result exactly). On a leaf-spine fabric its
    links count as two more resources."""
    if t.links is not None:
        return _greedy_fill_links(t, order, live, avail_s, avail_r,
                                  avail_x, rates)
    src, dst = t.src, t.dst
    ordered = order[live[order]]
    for _ in range(2 * t.num_ports + 2):
        if ordered.size == 0:
            break
        ok = (avail_s[src[ordered]] > 0.0) & (avail_r[dst[ordered]] > 0.0)
        cand = ordered[ok]
        if cand.size == 0:
            break
        first = np.ones(cand.size, bool)
        for k in (src[cand], dst[cand]):
            f = np.zeros(cand.size, bool)
            f[np.unique(k, return_index=True)[1]] = True
            first &= f
        take = cand[first]
        r = np.minimum(avail_s[src[take]], avail_r[dst[take]])
        rates[take] = r
        avail_s[src[take]] -= r
        avail_r[dst[take]] -= r
        ordered = cand[~first]


def _greedy_fill_links(t: Table, order, live, avail_s, avail_r, avail_x,
                       rates) -> None:
    """The D4 walk over ports and leaf links: a flow is taken in the
    round in which it is the first in order on its two ports and on
    each link it crosses (an intra-leaf flow on none), at the least
    residual of them all."""
    src, dst, up, dn = t.src, t.dst, t.links.up, t.links.dn
    Lx = avail_x.shape[0]
    ordered = order[live[order]]
    for _ in range(2 * (t.num_ports + Lx) + 2):
        if ordered.size == 0:
            break
        u, d = up[ordered], dn[ordered]
        ok = (avail_s[src[ordered]] > 0.0) & (avail_r[dst[ordered]] > 0.0)
        ok &= (u < 0) | (avail_x[np.maximum(u, 0)] > 0.0)
        ok &= (d < 0) | (avail_x[np.maximum(d, 0)] > 0.0)
        cand = ordered[ok]
        if cand.size == 0:
            break
        # an intra-leaf flow gets an id of its own on each link axis, so
        # that it is the first there
        fresh = Lx + np.arange(cand.size)
        first = np.ones(cand.size, bool)
        for k in (src[cand], dst[cand], np.where(up[cand] >= 0, up[cand],
                                                 fresh),
                  np.where(dn[cand] >= 0, dn[cand], fresh)):
            f = np.zeros(cand.size, bool)
            f[np.unique(k, return_index=True)[1]] = True
            first &= f
        take = cand[first]
        r = np.minimum(avail_s[src[take]], avail_r[dst[take]])
        tu, td = up[take], dn[take]
        mu, md = tu >= 0, td >= 0
        r = np.minimum(r, np.where(mu, avail_x[np.maximum(tu, 0)], np.inf))
        r = np.minimum(r, np.where(md, avail_x[np.maximum(td, 0)], np.inf))
        rates[take] = r
        avail_s[src[take]] -= r
        avail_r[dst[take]] -= r
        avail_x[tu[mu]] -= r[mu]
        avail_x[td[md]] -= r[md]
        ordered = cand[~first]


def maxmin_fill(t: Table, cand, avail_s, avail_r, avail_x) -> np.ndarray:
    """Max-min fair rates for the flows in `cand` over the residual
    port and link capacities (updated in place), by progressive
    filling: each round raises every unfrozen flow to the least fair
    level of a port or link and freezes the flows of those that it
    saturates."""
    src, dst, up, dn = t.src, t.dst, t.links.up, t.links.dn
    P, Lx = t.num_ports, avail_x.shape[0]
    up_ok, dn_ok = up >= 0, dn >= 0
    rates = np.zeros(t.size.shape[0])
    frozen = ~cand
    for _ in range(2 * (P + Lx) + 2):
        if frozen.all():
            break
        act = ~frozen
        cnt_s = np.bincount(src[act], minlength=P)
        cnt_r = np.bincount(dst[act], minlength=P)
        cnt_x = (np.bincount(up[act & up_ok], minlength=Lx)
                 + np.bincount(dn[act & dn_ok], minlength=Lx))
        with np.errstate(divide="ignore", invalid="ignore"):
            lvl_s = np.where(cnt_s > 0, avail_s / np.maximum(cnt_s, 1),
                             np.inf)
            lvl_r = np.where(cnt_r > 0, avail_r / np.maximum(cnt_r, 1),
                             np.inf)
            lvl_x = np.where(cnt_x > 0, avail_x / np.maximum(cnt_x, 1),
                             np.inf)
        lvl = min(min(lvl_s.min(), lvl_r.min()), lvl_x.min())
        if not np.isfinite(lvl):
            break
        sat_s = (lvl_s <= lvl + 1e-12) & (cnt_s > 0)
        sat_r = (lvl_r <= lvl + 1e-12) & (cnt_r > 0)
        sat_x = (lvl_x <= lvl + 1e-12) & (cnt_x > 0)
        hit = act & (sat_s[src] | sat_r[dst])
        hit |= act & ((up_ok & sat_x[np.maximum(up, 0)])
                      | (dn_ok & sat_x[np.maximum(dn, 0)]))
        if not hit.any():
            break
        rates[hit] = lvl
        np.subtract.at(avail_s, src[hit], lvl)
        np.subtract.at(avail_r, dst[hit], lvl)
        np.maximum(avail_s, 0.0, out=avail_s)
        np.maximum(avail_r, 0.0, out=avail_r)
        np.subtract.at(avail_x, up[hit & up_ok], lvl)
        np.subtract.at(avail_x, dn[hit & dn_ok], lvl)
        np.maximum(avail_x, 0.0, out=avail_x)
        frozen |= hit
    return rates


class Saath:
    """The Fig. 7 coordinator with D1-D5 and the §4.3 re-queue."""

    def __init__(self, p: Params, t: Table):
        self.p = p
        self.queue = np.full(t.C, -1)
        self.deadline = np.full(t.C, np.inf)
        self.running = np.zeros(t.C, bool)
        self._med: dict = {}

    def _queues(self, t: Table) -> np.ndarray:
        """Eq. 1 queues; with §4.3, a coflow with both finished and live
        flows is re-queued by its remaining length estimated from the
        median finished-flow size (m_hat = median - least sent of its
        unfinished flows)."""
        p = self.p
        m = np.zeros(t.C)
        np.maximum.at(m, t.cid, t.sent)
        q = queue_of(m * t.width, p)
        if p.dynamics_requeue:
            live = t.flow_live()
            done_f = t.done & t.active[t.cid]
            ndone = np.bincount(t.cid[done_f], minlength=t.C)
            mixed = ((ndone > 0)
                     & (np.bincount(t.cid[live], minlength=t.C) > 0)
                     & t.active)
            if mixed.any():
                least = np.full(t.C, np.inf)
                np.minimum.at(least, t.cid[live], t.sent[live])
                cs = np.nonzero(mixed)[0]
                f_e = np.array([self._median(t, c, ndone[c]) for c in cs])
                m_hat = np.maximum(f_e - least[cs], 0.0)
                q[cs] = queue_of(m_hat * t.width[cs], p)
        return q

    def _median(self, t: Table, c: int, ndone: int) -> float:
        """Median finished-flow size of coflow c, recomputed only when
        another of its flows has finished."""
        hit = self._med.get(c)
        if hit is None or hit[0] != ndone:
            lo, hi = t.flow_lo[c], t.flow_hi[c]
            hit = (ndone, float(np.median(t.size[lo:hi][t.done[lo:hi]])))
            self._med[c] = hit
        return hit[1]

    def schedule(self, t: Table, now: float) -> np.ndarray:
        p = self.p
        live = t.flow_live()
        rates = np.zeros(t.size.shape[0])
        q_new = self._queues(t)
        entered = t.active & (q_new != self.queue)
        if entered.any():
            cq = np.bincount(q_new[t.active], minlength=p.num_queues)
            t_min = min_queue_residence(q_new, t.width, p)
            for c in np.nonzero(entered)[0]:
                self.deadline[c] = now + p.deadline_factor \
                    * max(cq[q_new[c]], 1) * t_min[c]
        self.queue = np.where(t.active, q_new, self.queue)

        # per-port arithmetic on the active coflows only (row j of the
        # local arrays is coflow act[j]); finished ones never contend
        P = t.num_ports
        act = np.nonzero(t.active)[0]
        loc = np.full(t.C, -1)
        loc[act] = np.arange(act.size)
        lc, ls, ld = loc[t.cid[live]], t.src[live], t.dst[live]
        A_s = np.zeros((act.size, P), bool)
        A_r = np.zeros((act.size, P), bool)
        A_s[lc, ls] = True
        A_r[lc, ld] = True
        k = np.zeros(t.C, np.int64)
        k[act] = contention(A_s, A_r, np.ones(act.size, bool))
        expired = t.active & (now >= self.deadline)
        key = [(0, self.deadline[c], 0, 0, t.arrival[c], c) if expired[c]
               else (1, q_new[c], k[c], int(not self.running[c]),
                     t.arrival[c], c) for c in act]
        order = act[sorted(range(len(act)), key=lambda i: key[i])]

        cnt_s = np.zeros((act.size, P), np.int64)
        cnt_r = np.zeros((act.size, P), np.int64)
        np.add.at(cnt_s, (lc, ls), 1)
        np.add.at(cnt_r, (lc, ld), 1)
        avail_s, avail_r = t.bw.copy(), t.bw.copy()
        links = t.links
        avail_x = cnt_x = None
        if links is not None:
            # each inter-leaf flow counts once on its uplink and once
            # on its downlink
            avail_x = links.cap.copy()
            cnt_x = np.zeros((act.size, avail_x.shape[0]), np.int64)
            lf = live & (links.up >= 0)
            np.add.at(cnt_x, (loc[t.cid[lf]], links.up[lf]), 1)
            np.add.at(cnt_x, (loc[t.cid[lf]], links.dn[lf]), 1)
        admitted = np.zeros(t.C, bool)
        missed = []
        for c in order:
            j = loc[c]
            cs, cr = cnt_s[j], cnt_r[j]
            ps, pr = cs > 0, cr > 0
            if not ps.any() and not pr.any():
                continue
            r = np.inf
            if ps.any():
                r = min(r, (avail_s[ps] / cs[ps]).min())
            if pr.any():
                r = min(r, (avail_r[pr] / cr[pr]).min())
            if links is not None:
                cx = cnt_x[j]
                px = cx > 0
                if px.any():
                    r = min(r, (avail_x[px] / cx[px]).min())
            if r < p.min_rate or r <= 0.0:
                missed.append(c)
                continue
            lo, hi = t.flow_lo[c], t.flow_hi[c]
            rates[lo:hi][live[lo:hi]] = r
            avail_s -= r * cs
            avail_r -= r * cr
            if links is not None:
                avail_x -= r * cnt_x[j]
            admitted[c] = True

        if p.work_conservation and missed:
            order_f = np.concatenate([np.arange(t.flow_lo[c], t.flow_hi[c])
                                      for c in missed])
            if links is not None and links.maxmin:
                cand = np.zeros(live.shape, bool)
                cand[order_f] = True
                rates += maxmin_fill(t, cand & live, avail_s, avail_r,
                                     avail_x)
            else:
                greedy_fill(t, order_f, live, avail_s, avail_r, rates,
                            avail_x)
        self.running = admitted
        return rates

    def next_event(self, t: Table, now: float, rates) -> float:
        """Earliest queue-threshold crossing or deadline expiry."""
        live = t.flow_live()
        out = math.inf
        q = np.maximum(self.queue[t.cid], 0)
        lim = self.p.thresholds()[q] / np.maximum(t.width[t.cid], 1)
        with np.errstate(divide="ignore", invalid="ignore"):
            dt = np.where(live & (rates > 0) & np.isfinite(lim),
                          (lim - t.sent) / rates, np.inf)
        dt = dt[dt > 1e-12]
        if dt.size:
            out = min(out, now + float(dt.min()))
        dl = self.deadline[t.active & (self.deadline > now + 1e-12)]
        if dl.size:
            out = min(out, float(dl.min()))
        return out


def _up(x: float, delta: float) -> float:
    return math.ceil(x / delta - 1e-9) * delta


def _integrate(t: Table, rates, live, now: float, t_next: float) -> None:
    adv = rates * (t_next - now)
    rem = t.size - t.sent
    fin = live & (adv >= rem - 1e-9) & (rates > 0)
    if fin.any():
        t.fct[fin] = now + rem[fin] / rates[fin]
        t.done[fin] = True
        t.sent[fin] = t.size[fin]
    grow = live & ~fin
    t.sent[grow] = np.minimum(t.size[grow], t.sent[grow] + adv[grow])
    for c in np.unique(t.cid[fin]):
        lo, hi = t.flow_lo[c], t.flow_hi[c]
        if t.done[lo:hi].all() and not t.finished[c]:
            t.finished[c] = True
            t.active[c] = False
            t.cct[c] = float(np.nanmax(t.fct[lo:hi])) - t.arrival[c]


def run(coflows, params: dict, num_ports: int, *,
        until: float, work_conservation: Optional[bool] = None,
        topology: Optional[dict] = None) -> np.ndarray:
    """Replay `coflows` (objects with arrival, src, dst, size) until the
    first δ tick at or past `until`; returns each coflow's CCT (NaN if
    it had not finished). `work_conservation=False` switches D4 off;
    `topology` is a configuration's leaf-spine entry (None: a big
    switch)."""
    if not coflows:
        return np.zeros(0)
    p = Params(**params)
    if work_conservation is not None:
        p.work_conservation = work_conservation
    t = Table(coflows, num_ports, p.port_bw, topology)
    pol = Saath(p, t)
    arrivals = np.unique(t.arrival)
    now = _up(float(arrivals[0]), p.delta)
    max_jump = 200 * p.delta
    while now < until - 1e-9:
        t.active[:] = (t.arrival <= now + 1e-12) & ~t.finished
        if t.finished.all():
            break
        live = t.flow_live()
        future = arrivals[arrivals > now + 1e-12]
        next_arrival = float(future[0]) if future.size else math.inf
        if not live.any():
            if math.isinf(next_arrival):
                break
            now = _up(next_arrival, p.delta)
            continue
        rates = pol.schedule(t, now)
        t_ev = min(next_arrival, pol.next_event(t, now, rates),
                   now + max_jump)
        srv = live & (rates > 0)
        if srv.any():
            t_ev = min(t_ev, float((now + (t.size[srv] - t.sent[srv])
                                    / rates[srv]).min()))
        if math.isinf(t_ev):
            raise RuntimeError(f"reference deadlocked at t={now}")
        t_next = max(_up(t_ev, p.delta), now + p.delta)
        _integrate(t, rates, live, now, t_next)
        now = t_next
    return t.cct
