"""The multi-tenant coflow serving front door (DESIGN.md §8).

`CoflowServer` is the admission-controlled service surface of the
scheduling plane: tenants register by name, submit coflows, and poll
completions, while ONE `repro.api.SessionPool` hosts every tenant as a
row of a single batched device-resident slab — `advance(dt)` moves the
whole fleet's coordinators with one vmapped dispatch chain, which is
what keeps the per-decision cost flat as tenant count grows (the
property PAPER.md §5 / Table 2 measures on the testbed coordinator).

Admission model: `max_tenants` fixes the slab's row count up front
(the compiled executables are shaped by it); `register` raises
`AdmissionError` once the cap is reached, and `evict` frees a row —
dropping the tenant's unfinished coflows — for the next registrant.
Tenants may register with their OWN `SchedulerParams`/mechanism
switches (`register(name, params=..., mechanisms=...)`): the pool
stacks one parameter row per tenant, so a heterogeneous fleet still
rides one dispatch. Per-tenant outcomes are extracted as the SAME
normalized `repro.api.Result` the offline engines produce
(`api.scenario.result_from_completions`), so `avg_cct`, `makespan`,
`summary()` and `benchmarks.common.record` work unchanged on live
serving data.

Completion retention is BOUNDED: every harvested completion is folded
into the tenant's incremental `TenantAggregates` (exact lifetime
count / mean CCT / makespan, O(1) memory), and the raw
`CompletedCoflow` records are TRIMMED once `poll` returns them (plus a
`history_limit` backstop for tenants that never poll). `result()`
therefore reports exact lifetime aggregates forever, while its
per-coflow arrays cover the retained (not-yet-polled) window — a
long-lived tenant no longer grows the server without bound.

Harvesting rides the pool's NEW-COMPLETION BITMAP
(`SessionPool.completed_sessions`): `advance` polls only tenants whose
row finished something since the last harvest, so a clean tenant costs
ZERO host work per fleet step (previously every advance probed every
tenant with a per-session `poll()`).

Overload shedding (ISSUE 6): a tenant may register under a
`TenantQuota` — live-coflow / live-byte budgets plus an SLO. A
`submit` that would blow the budget is SHED under ``policy="reject"``
(the whole batch is refused with `QuotaExceededError` — nothing is
partially admitted) or DEFERRED under ``policy="defer"`` (the
in-budget prefix is admitted; the rest queues server-side and retries
on every `advance` as capacity frees up, arrivals clamping to the
tenant clock). A deferred submission that waits longer than the
quota's `slo` is shed instead of admitted — the DCoflow-style
degradation (PAPERS.md, arxiv 2205.01229): work that can no longer
meet its budget is dropped with a counted decision, not queued into
unbounded latency. Shed/deferral counters live in `TenantAggregates`
(`shed`, `deferred`) and fleet-wide in `stats()`.

The underlying pool's sharded slab, async dispatch and fabric model
pass straight through: ``CoflowServer(..., shards=N,
async_dispatch=..., features=..., topology=...)``.

CLI demo (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --tenants 6 --seconds 0.4

(The LM prefill/decode serving driver formerly here lives in
`repro.launch.lm_serve`.)
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import sys
import time
from typing import Dict, List, Optional, Sequence

if __name__ == "__main__":
    # before the `repro.api` import below initializes jax
    from repro.launch.entry import force_host_devices

    force_host_devices(sys.argv)

import numpy as np
from jax.profiler import TraceAnnotation

from repro.api import Result, SessionPool, result_from_completions
from repro.api.pool import PoolFullError
from repro.api.session import CompletedCoflow
from repro.core.coflow import Coflow
from repro.core.params import SchedulerParams
from repro.launch.entry import enable_compile_cache

# host spans of the front door, on the profiler's clock; the pool's own
# spans (`repro.api.pool.SPAN_*`) nest inside `advance`
SPAN_SUBMIT = "saath.server.submit"
SPAN_ADVANCE = "saath.server.advance"
SPAN_HARVEST = "saath.server.harvest"
SPAN_ADMIT_DEFERRED = "saath.server.admit_deferred"


class AdmissionError(RuntimeError):
    """The server is at its tenant admission cap."""


class QuotaExceededError(RuntimeError):
    """A submit was shed: it would blow the tenant's quota and the
    tenant registered under ``policy="reject"``."""


@dataclasses.dataclass(frozen=True)
class TenantQuota:
    """A tenant's overload budget: live-load caps plus an SLO.

    `max_live_coflows` / `max_live_bytes` bound the tenant's LIVE load
    (unfinished coflows on its row); a submit that would exceed either
    is shed (``policy="reject"``: the whole batch raises
    `QuotaExceededError`) or deferred (``policy="defer"``: the
    in-budget prefix is admitted, the overflow queues server-side and
    retries each `advance`). `slo` is the deferral deadline in tenant
    seconds: a deferred submission older than it is shed — by then it
    cannot meet its latency target, so admitting it only grows the
    backlog (the DCoflow admission rule shape)."""
    max_live_coflows: Optional[int] = None
    max_live_bytes: Optional[float] = None
    slo: Optional[float] = None
    policy: str = "reject"

    def __post_init__(self):
        if self.policy not in ("reject", "defer"):
            raise ValueError(
                f"quota policy must be 'reject' or 'defer', "
                f"got {self.policy!r}")
        for name in ("max_live_coflows", "max_live_bytes", "slo"):
            v = getattr(self, name)
            if v is not None and v <= 0:
                raise ValueError(f"{name} must be positive, got {v}")


@dataclasses.dataclass
class TenantAggregates:
    """Exact lifetime completion statistics, folded incrementally as
    completions are harvested (O(1) memory however long the tenant
    lives — the fix for the unbounded per-tenant history)."""
    coflows: int = 0
    flows: int = 0
    bytes: float = 0.0
    cct_sum: float = 0.0
    last_fct: float = -math.inf     # max absolute flow completion time
    trimmed: int = 0                # records dropped by history_limit
    shed: int = 0                   # coflows refused over quota/SLO
    deferred: int = 0               # coflows queued by policy="defer"

    def fold(self, comps: Sequence[CompletedCoflow]) -> None:
        for d in comps:
            self.coflows += 1
            self.flows += int(d.fct.size)
            if d.size is not None:
                self.bytes += float(np.sum(d.size))
            self.cct_sum += float(d.cct)
            if d.fct.size:
                self.last_fct = max(self.last_fct,
                                    float(np.max(d.fct)))

    @property
    def avg_cct(self) -> float:
        return self.cct_sum / self.coflows if self.coflows \
            else float("nan")

    @property
    def makespan(self) -> float:
        # guard on `last_fct` being FINITE, not on `coflows`: a fold of
        # completions that all carry zero flows (fct.size == 0) bumps
        # `coflows` without ever touching `last_fct`, and the bare
        # coflows-gate then reported the -inf initializer as a makespan
        if not math.isfinite(self.last_fct):
            return float("nan")
        return self.last_fct


@dataclasses.dataclass
class TenantResult(Result):
    """A tenant's normalized `Result` whose summary statistics come
    from the EXACT lifetime aggregates while the per-coflow arrays
    cover only the retained (not-yet-polled) completion window —
    `row_cct()`/percentiles see the window, `avg_cct`/`makespan`/
    `num_coflows`/`total_bytes` the whole registration."""
    agg: Optional[TenantAggregates] = None
    total_bytes: Optional[np.ndarray] = None   # (1,) lifetime bytes

    @property
    def avg_cct(self) -> np.ndarray:
        if self.agg is None:
            return Result.avg_cct.fget(self)
        return np.array([self.agg.avg_cct])

    @property
    def makespan(self) -> np.ndarray:
        if self.agg is None:
            return Result.makespan.fget(self)
        return np.array([self.agg.makespan])

    @staticmethod
    def from_window(window: Sequence[CompletedCoflow],
                    agg: TenantAggregates) -> "TenantResult":
        """Build from the retained completion window + the lifetime
        aggregates (counts lifted to the lifetime totals; the arrays
        may be shorter after trimming)."""
        base = result_from_completions(window, engine="jax",
                                       policy="saath")
        out = TenantResult(
            **{f.name: getattr(base, f.name)
               for f in dataclasses.fields(Result)},
            agg=agg if agg.coflows else None)
        if agg.coflows:
            out.num_coflows = np.array([agg.coflows])
            out.num_flows = np.array([agg.flows])
            out.total_bytes = np.array([agg.bytes])
        else:
            out.total_bytes = np.array([float(np.nansum(out.sent))])
        return out


class CoflowServer:
    """Admission-controlled multi-tenant coflow scheduling service.

    All tenants share one fabric (`num_ports` ports) and one compiled
    tick structure; each tenant owns an isolated `SaathSession` row of
    the server's `SessionPool` — optionally under its own scheduler
    parameters — and its coflows never contend with another tenant's
    row (the pool batches the COMPUTATION, not the fabric).

    `history_limit` bounds the raw completions retained per tenant
    between polls (aggregates stay exact past it; overflow is counted
    in `aggregates(tenant).trimmed`).
    """

    def __init__(self, params: Optional[SchedulerParams] = None, *,
                 num_ports: int, max_tenants: int = 16,
                 mechanisms: Optional[dict] = None,
                 kernel: Optional[str] = None, chunk: int = 32,
                 history_limit: int = 4096, shards: int = 1,
                 async_dispatch: bool = True,
                 features: Optional[tuple] = None, topology=None):
        self.pool = SessionPool(params, num_ports=num_ports,
                                max_sessions=max_tenants,
                                mechanisms=mechanisms, kernel=kernel,
                                chunk=chunk, shards=shards,
                                async_dispatch=async_dispatch,
                                features=features, topology=topology)
        self.history_limit = int(history_limit)
        self._tenants: Dict[str, object] = {}
        self._pending: Dict[str, List[CompletedCoflow]] = {}
        self._agg: Dict[str, TenantAggregates] = {}
        self._quota: Dict[str, Optional[TenantQuota]] = {}
        # policy="defer" overflow: (coflow, tenant clock at deferral)
        self._deferred: Dict[str, List[tuple]] = {}
        self._live_bytes: Dict[str, float] = {}
        self.rejected = 0

    # ---- admission -------------------------------------------------------

    @property
    def tenants(self) -> List[str]:
        return list(self._tenants)

    @property
    def occupancy(self) -> tuple:
        return (len(self._tenants), self.pool.max_sessions)

    def register(self, tenant: str,
                 params: Optional[SchedulerParams] = None,
                 mechanisms: Optional[dict] = None,
                 quota: Optional[TenantQuota] = None) -> None:
        """Admit a tenant (raises `AdmissionError` at the cap,
        `ValueError` on a duplicate name), optionally under its own
        `SchedulerParams`/mechanism switches — the tenant's slab row
        then schedules with those thresholds/δ/switches inside the
        same fleet dispatch — and/or a `TenantQuota` overload budget."""
        if tenant in self._tenants:
            raise ValueError(f"tenant {tenant!r} is already registered")
        try:
            # the ONE admission authority. ONLY the pool-full signal is
            # an admission decision; any other fault (bad params raise
            # ValueError, engine faults raise their own RuntimeError)
            # propagates untouched — translating it here misreported
            # real bugs as "admission cap reached" and corrupted the
            # `rejected` counter
            sess = self.pool.session(params=params,
                                     mechanisms=mechanisms)
        except PoolFullError as e:
            self.rejected += 1
            used, cap = self.occupancy
            raise AdmissionError(
                f"admission cap reached ({used}/{cap} tenants); evict "
                f"one or raise max_tenants") from e
        self._tenants[tenant] = sess
        self._pending[tenant] = []
        self._agg[tenant] = TenantAggregates()
        self._quota[tenant] = quota
        self._deferred[tenant] = []
        self._live_bytes[tenant] = 0.0

    def evict(self, tenant: str) -> None:
        """Release a tenant's row (unfinished coflows are dropped)."""
        sess = self._session(tenant)
        self.pool.release(sess)
        del self._tenants[tenant]
        del self._pending[tenant]
        del self._agg[tenant]
        del self._quota[tenant]
        del self._deferred[tenant]
        del self._live_bytes[tenant]

    def _session(self, tenant: str):
        try:
            return self._tenants[tenant]
        except KeyError:
            raise KeyError(
                f"unknown tenant {tenant!r}; registered: "
                f"{sorted(self._tenants)}") from None

    # ---- the tenant-keyed session surface --------------------------------

    def submit(self, tenant: str, coflows: Sequence[Coflow]) -> List[int]:
        """Submit coflows to a tenant's row, under its quota when one
        was registered: an over-budget batch is refused whole with
        `QuotaExceededError` (``policy="reject"``) or split — in-budget
        prefix admitted now, overflow deferred server-side
        (``policy="defer"``). Returns the handles admitted NOW (a
        deferred coflow gets its handle when a later `advance` admits
        it)."""
        with TraceAnnotation(SPAN_SUBMIT):
            sess = self._session(tenant)
            quota = self._quota[tenant]
            coflows = list(coflows)
            if quota is None:
                handles = sess.submit(coflows)
                self._live_bytes[tenant] += sum(c.total_bytes for c in coflows)
                return handles
            agg = self._agg[tenant]
            fits = self._budget_room(tenant, coflows)
            if fits < len(coflows) and quota.policy == "reject":
                agg.shed += len(coflows)
                raise QuotaExceededError(
                    f"tenant {tenant!r} over quota ({sess.num_live} live "
                    f"coflows, {self._live_bytes[tenant]:.3g} live bytes); "
                    f"batch of {len(coflows)} shed")
            admit, overflow = coflows[:fits], coflows[fits:]
            handles = sess.submit(admit) if admit else []
            self._live_bytes[tenant] += sum(c.total_bytes for c in admit)
            if overflow:
                now = sess.now
                self._deferred[tenant].extend((c, now) for c in overflow)
                agg.deferred += len(overflow)
            return handles

    def _budget_room(self, tenant: str,
                     coflows: Sequence[Coflow]) -> int:
        """How many of `coflows` (in order) fit the tenant's quota
        right now — greedy prefix against the live-coflow and
        live-byte budgets."""
        quota = self._quota[tenant]
        live = self._tenants[tenant].num_live
        live_b = self._live_bytes[tenant]
        n = 0
        for c in coflows:
            if quota.max_live_coflows is not None and \
                    live + 1 > quota.max_live_coflows:
                break
            if quota.max_live_bytes is not None and \
                    live_b + c.total_bytes > quota.max_live_bytes:
                break
            live += 1
            live_b += c.total_bytes
            n += 1
        return n

    def _harvest(self, tenant: str) -> None:
        """Drain the session's fresh completions into the tenant's
        bounded pending buffer, folding the exact aggregates first."""
        with TraceAnnotation(SPAN_HARVEST):
            done = self._tenants[tenant].poll()
            if not done:
                return
            agg = self._agg[tenant]
            before = agg.bytes
            agg.fold(done)
            self._live_bytes[tenant] = max(
                0.0, self._live_bytes[tenant] - (agg.bytes - before))
            pend = self._pending[tenant]
            pend.extend(done)
            if len(pend) > self.history_limit:
                drop = len(pend) - self.history_limit
                del pend[:drop]
                agg.trimmed += drop

    def advance(self, dt: float) -> float:
        """Advance EVERY tenant's clock by `dt` with one pooled
        dispatch, harvesting completions into the per-tenant buffers.
        Harvesting walks the pool's NEW-COMPLETION BITMAP
        (`completed_sessions`), not the tenant roster: a tenant whose
        row finished nothing since the last harvest is never polled —
        zero host work per clean tenant per step. Deferred submissions
        are then retried against the freed budget."""
        with TraceAnnotation(SPAN_ADVANCE):
            self.pool.advance(dt)
            fresh = {id(s) for s in self.pool.completed_sessions()}
            if fresh:
                for tenant, sess in self._tenants.items():
                    if id(sess) in fresh:
                        self._harvest(tenant)
            self._admit_deferred()
            return dt

    def _admit_deferred(self) -> None:
        """Retry each tenant's deferred queue (in deferral order):
        entries older than the quota's SLO are shed — they can no
        longer meet their target, so admitting them only grows the
        backlog — and the rest are admitted while the freed budget
        lasts (arrivals clamp to the tenant clock on submit)."""
        with TraceAnnotation(SPAN_ADMIT_DEFERRED):
            for tenant, queue in self._deferred.items():
                if not queue:
                    continue
                sess = self._tenants[tenant]
                quota = self._quota[tenant]
                agg = self._agg[tenant]
                now = sess.now
                keep: List[tuple] = []
                blocked = False
                for c, t_defer in queue:
                    if quota.slo is not None and now - t_defer > quota.slo:
                        agg.shed += 1
                        continue
                    if not blocked and self._budget_room(tenant, [c]):
                        sess.submit([c])
                        self._live_bytes[tenant] += c.total_bytes
                    else:
                        blocked = True    # keep the queue order: nothing
                        keep.append((c, t_defer))  # younger jumps ahead
                self._deferred[tenant] = keep

    def poll(self, tenant: str) -> List[CompletedCoflow]:
        """Completions for `tenant` not yet returned by a poll. This is
        the TRIM point: returned records leave the server (their
        statistics live on in `aggregates(tenant)`)."""
        self._session(tenant)
        self._harvest(tenant)
        out = self._pending[tenant]
        self._pending[tenant] = []
        return out

    def num_live(self, tenant: str) -> int:
        return self._session(tenant).num_live

    def aggregates(self, tenant: str) -> TenantAggregates:
        """The tenant's exact lifetime completion statistics (stable
        across polls/trimming; O(1) memory)."""
        self._session(tenant)
        self._harvest(tenant)
        return self._agg[tenant]

    def result(self, tenant: str) -> Result:
        """The tenant's completions as a normalized `repro.api.Result`
        (the offline engines' NaN/padding contract: an idle tenant
        reports NaN aggregates, never 0.0). A pure accessor: it does
        NOT advance the `poll` cursor. `avg_cct`/`makespan`/
        `num_coflows` are exact over the tenant's WHOLE registration
        (incremental aggregates); the per-coflow arrays cover the
        retained not-yet-polled window."""
        self._session(tenant)
        self._harvest(tenant)
        return TenantResult.from_window(self._pending[tenant],
                                        self._agg[tenant])

    def stats(self) -> dict:
        used, cap = self.occupancy
        return {
            "tenants": used, "max_tenants": cap,
            "rejected": self.rejected,
            "live_coflows": sum(s.num_live
                                for s in self._tenants.values()),
            "completed": sum(a.coflows for a in self._agg.values()),
            "retained": sum(len(p) for p in self._pending.values()),
            "shed": sum(a.shed for a in self._agg.values()),
            "deferred": sum(a.deferred for a in self._agg.values()),
            "deferred_pending": sum(len(q)
                                    for q in self._deferred.values()),
            "shards": self.pool.shards,
            "slab": (self.pool._C_cap, self.pool._F_cap),
        }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        description="multi-tenant coflow serving demo")
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--max-tenants", type=int, default=4,
                    help="admission cap (< --tenants demonstrates "
                    "rejection + eviction)")
    ap.add_argument("--seconds", type=float, default=0.4,
                    help="virtual horizon per tenant")
    ap.add_argument("--ports", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the slab row axis across this many "
                    "devices (CPU: forced host devices)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.traces.synth import tiny_trace

    params = SchedulerParams(port_bw=1e9, delta=1e-3,
                             start_threshold=1e6)
    if args.max_tenants % args.shards:
        ap.error("--max-tenants must be a multiple of --shards")
    srv = CoflowServer(params, num_ports=args.ports,
                       max_tenants=args.max_tenants,
                       shards=args.shards)
    t0 = time.perf_counter()
    waiting = [f"tenant/{i}" for i in range(args.tenants)]
    admitted: List[str] = []
    pending: Dict[str, list] = {}
    for i, name in enumerate(list(waiting)):
        try:
            srv.register(name)
        except AdmissionError:
            continue
        waiting.remove(name)
        admitted.append(name)
        tr = tiny_trace(16, args.ports, seed=args.seed + i, load=0.5)
        pending[name] = sorted(tr.coflows, key=lambda c: c.arrival)

    steps = 0
    next_seed = args.seed + args.tenants
    while admitted or waiting:
        srv.advance(args.seconds / 8)
        steps += 1
        for name in list(admitted):
            sess = srv._tenants[name]
            while pending[name] and pending[name][0].arrival <= sess.now:
                srv.submit(name, [pending[name].pop(0)])
            if not pending[name] and srv.num_live(name) == 0:
                res = srv.result(name)
                print(f"  {name}: {int(res.num_coflows[0])} coflows, "
                      f"avg_cct={res.avg_cct[0] * 1e3:.2f}ms, "
                      f"makespan={res.makespan[0] * 1e3:.1f}ms")
                srv.evict(name)       # frees the row for a waiter
                admitted.remove(name)
                if waiting:
                    nxt = waiting.pop(0)
                    srv.register(nxt)
                    admitted.append(nxt)
                    tr = tiny_trace(16, args.ports, seed=next_seed,
                                    load=0.5)
                    next_seed += 1
                    pending[nxt] = sorted(tr.coflows,
                                          key=lambda c: c.arrival)
        if steps > 10000:
            raise RuntimeError("demo failed to drain")
    wall = time.perf_counter() - t0
    out = dict(srv.stats(), wall_seconds=wall, steps=steps)
    print(f"== served {args.tenants} tenants through a "
          f"{args.max_tenants}-row slab in {wall:.2f}s "
          f"({steps} fleet steps; slab {out['slab']}) ==")
    return out


if __name__ == "__main__":
    main()
