"""Jitted Saath coordinator — the in-framework scheduler.

The numpy Saath in ``core.policies.saath`` is the trace-replay reference;
this module is the same Fig. 7 algorithm vectorized over fixed-size padded
arrays so one coordinator tick is a single XLA computation (with the LCoF
contention as the ``kernels.contention`` Pallas kernel on TPU). It is used

* by the framework plane: between train steps the coordinator re-plans
  the issue order of collective coflows (gradient buckets, MoE a2a waves,
  checkpoint uploads, KV migrations) — ``runtime.coflow_bridge``;
* by ``benchmarks/table2_coordinator_latency.py`` to reproduce the
  paper's coordinator-cost table at 512-port x 4k-coflow scale.

Granularity: one row per COFLOW with per-port live-flow counts
(cnt_s/cnt_r) drives queue assignment, LCoF ordering, deadlines and the
all-or-none admission. Work conservation runs at FLOW granularity when
the caller supplies a ``FlowView`` (the reference's ``greedy_flow_alloc``
semantics: a strict subset of a missed coflow's flows can be rescued);
without one it falls back to the coflow-granular equal-rate fill, which
is the faithful mapping for collective coflows where a partial issue is
meaningless (DESIGN.md §2). The §4.3 cluster-dynamics re-queue is driven
by the caller-computed finished-flow median estimate (``batch.mixed`` /
``batch.m_dyn``) and gated by ``DynCoordParams.requeue``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.queues import CROSS_EPS
from repro.kernels import ops

BIG = jnp.float32(1e30)

# `jax.named_scope`s of the tick's parts: every op of a compiled tick
# carries the innermost one in its metadata (`op_name`), which is how a
# device trace attributes time to the code that spent it
SCOPE_QUEUES = "saath.tick.queues"
SCOPE_CONTENTION = "saath.tick.contention"
SCOPE_ORDER = "saath.tick.order"
SCOPE_ADMIT = "saath.tick.admit"
SCOPE_WC_ORDER = "saath.tick.wc_order"
SCOPE_WC_FILL = "saath.tick.wc_fill"


class CoordParams(NamedTuple):
    """Static coordinator parameters (see core.params.SchedulerParams)."""
    thresholds: tuple          # (K,) Q_q^hi, last = +inf
    deadline_factor: float = 2.0
    min_rate_frac: float = 1e-3
    bw_ref: float = 1.0        # reference port bandwidth for t_min
    growth: float = 0.0        # E; 0 = infer from thresholds (legacy)
    # mechanism switches (traced 0/1 scalars in DynCoordParams, so a
    # parameter sweep can vmap over them instead of recompiling)
    work_conservation: bool = True   # D4 leftover-bandwidth fill
    dynamics_requeue: bool = True    # §4.3 median-based re-queue
    lcof: bool = True                # LCoF contention ordering (Fig. 10)
    per_flow_threshold: bool = True  # Eq. 1 vs Aalo total-bytes queues
    clairvoyant: bool = True         # False = pilot-sampling estimates

    @staticmethod
    def from_params(p) -> "CoordParams":
        return CoordParams(
            tuple(p.thresholds()), p.deadline_factor,
            p.min_rate_frac, p.port_bw, p.growth,
            work_conservation=getattr(p, "work_conservation", True),
            dynamics_requeue=getattr(p, "dynamics_requeue", True),
            clairvoyant=getattr(p, "clairvoyant", True))


def _queue_spans(thresholds, growth: float = 0.0) -> list:
    """Per-queue residence spans (matches core.queues.min_queue_residence):
    span_q = Q_q^hi - Q_q^lo; the unbounded last queue uses one growth
    step beyond its lower bound. `growth` must be passed explicitly for
    K == 2, where thresholds[1] is +inf and cannot be used to infer E."""
    K = len(thresholds)
    los = (0.0,) + tuple(thresholds[:-1])
    if not growth:
        growth = (thresholds[1] / thresholds[0]) if K > 2 else 2.0
    spans = [h - l for h, l in zip(thresholds, los)]
    spans[K - 1] = (los[K - 1] * growth - los[K - 1]) if K > 1 \
        else thresholds[0]
    return spans


class DynCoordParams(NamedTuple):
    """Coordinator parameters as traced arrays.

    Same knobs as CoordParams but every leaf is a jax array, so a
    parameter sweep can be vmapped (stack a leading axis on each leaf)
    instead of recompiling per setting. K = len(thresholds) stays a
    static shape. Built host-side: spans are precomputed with plain
    python so the traced tick never sees the +inf arithmetic.
    """
    thresholds: jax.Array       # (K,) f32, last = +inf
    span: jax.Array             # (K,) f32 queue residence spans
    deadline_factor: jax.Array  # () f32
    min_rate_frac: jax.Array    # () f32
    bw_ref: jax.Array           # () f32
    wc: jax.Array               # () f32 1 = work conservation on
    requeue: jax.Array          # () f32 1 = §4.3 dynamics re-queue on
    lcof: jax.Array             # () f32 1 = LCoF ordering (0 = FIFO-in-q)
    per_flow: jax.Array         # () f32 1 = Eq. 1 per-flow thresholds
    # Non-clairvoyant sampling leaf. None = clairvoyance compiled OUT
    # (an empty pytree subtree — jaxprs bitwise-unchanged from before
    # the mechanism existed). An f32 scalar = vmappable mode switch:
    # 1 = clairvoyant (§4.3 exact-median re-queue), 0 = learned
    # (pilot-sampling re-queue via CoflowBatch.s_mixed/s_m).
    clairvoyant: jax.Array | None = None

    @staticmethod
    def from_params(p) -> "DynCoordParams":
        return DynCoordParams.from_cp(CoordParams.from_params(p))

    @staticmethod
    def from_cp(cp: CoordParams) -> "DynCoordParams":
        return DynCoordParams(
            jnp.asarray(cp.thresholds, jnp.float32),
            jnp.asarray(_queue_spans(cp.thresholds, cp.growth),
                        jnp.float32),
            jnp.float32(cp.deadline_factor),
            jnp.float32(cp.min_rate_frac),
            jnp.float32(cp.bw_ref),
            jnp.float32(1.0 if cp.work_conservation else 0.0),
            jnp.float32(1.0 if cp.dynamics_requeue else 0.0),
            jnp.float32(1.0 if cp.lcof else 0.0),
            jnp.float32(1.0 if cp.per_flow_threshold else 0.0),
            None if cp.clairvoyant else jnp.float32(0.0))


class CoordState(NamedTuple):
    queue: jax.Array     # (C,) int32, -1 = unseen
    deadline: jax.Array  # (C,) f32
    running: jax.Array   # (C,) bool — admitted in previous tick


def init_state(C: int) -> CoordState:
    return CoordState(jnp.full((C,), -1, jnp.int32),
                      jnp.full((C,), jnp.inf, jnp.float32),
                      jnp.zeros((C,), bool))


class CoflowBatch(NamedTuple):
    """One coordinator tick's view of the fabric (padded to C, P)."""
    active: jax.Array    # (C,) bool
    arrival: jax.Array   # (C,) int32 arrival RANK (host-computed, exact
    #                      FIFO order — float arrivals may collide in f32)
    m: jax.Array         # (C,) f32  max bytes sent by any flow (Eq. 1)
    width: jax.Array     # (C,) int32 flow count N_c
    cnt_s: jax.Array     # (C,P) f32 live-flow counts at sender ports
    cnt_r: jax.Array     # (C,P) f32 live-flow counts at receiver ports
    bw_s: jax.Array      # (P,) f32
    bw_r: jax.Array      # (P,) f32
    # optional refinements (None = mechanism unavailable this tick):
    total: jax.Array | None = None  # (C,) f32 total bytes sent (Aalo
    #                      queues for the per_flow_threshold=0 ablation)
    mixed: jax.Array | None = None  # (C,) bool — has BOTH finished and
    #                      live flows (§4.3 re-queue candidates)
    m_dyn: jax.Array | None = None  # (C,) f32 estimated remaining
    #                      length m_hat from the finished-flow median
    # leaf-spine fabric (DESIGN.md §11; None = big switch, the link
    # machinery is compiled out): per-(coflow, extra-link) live counts
    # and link capacities, uplinks stacked before downlinks (Lx = 2*Lf)
    cnt_x: jax.Array | None = None  # (C, Lx) f32
    bw_x: jax.Array | None = None   # (Lx,) f32
    # non-clairvoyant sampling (None = compiled out): pilot-learned
    # re-queue candidates and their estimated remaining length
    s_mixed: jax.Array | None = None  # (C,) bool — >=1 finished pilot
    #                      AND >=1 live flow (learned-mode §4.3)
    s_m: jax.Array | None = None    # (C,) f32 m_hat from the mean
    #                      finished-pilot size estimate


class FlowView(NamedTuple):
    """Per-flow companion to CoflowBatch for flow-granular work
    conservation. Flows are stored contiguous per coflow (the host
    layout shared with traces.batch), so a flow's priority inside the
    missed list is just (coflow priority, flow index) — no per-tick
    gather tables."""
    cid: jax.Array      # (F,) int32 owning coflow
    src: jax.Array      # (F,) int32 sender port
    dst: jax.Array      # (F,) int32 receiver port
    live: jax.Array     # (F,) bool
    # leaf-spine link ids (None = big switch): LOCAL leaf index in
    # [0, Lf], with Lf the "touches no shared link" sentinel — exactly
    # the TraceBatch.link_up/link_dn encoding
    up: jax.Array | None = None   # (F,) int32
    dn: jax.Array | None = None   # (F,) int32


def _queue_of(value: jax.Array, th: jax.Array) -> jax.Array:
    """Smallest q with value < Q_q^hi (th sorted, th[-1] = +inf).
    Applies core.queues.CROSS_EPS so exact-on-threshold landings (every
    crossing event lands there) decide identically to the f64 reference.
    """
    return jnp.searchsorted(th, value * (1.0 + CROSS_EPS),
                            side="right").astype(jnp.int32)


def _greedy_fill(flist: jax.Array, n_cand: jax.Array, resources):
    """The reference's sequential greedy fill (`greedy_flow_alloc`) over
    the priority-sorted flow list `flist`, whose first `n_cand` entries
    are the candidates: each takes the min of its resources' residuals.
    `resources` is a sequence of (residual vector, (F,) resource index
    of each flow id) pairs — sender and receiver ports, plus uplink and
    downlink on a leaf-spine fabric.

    A flow given r = min(residuals) > 0 leaves that minimum at exactly 0
    (x - x == 0 in float), and residuals never grow, so each rate
    saturates a resource for good: at most one flow per resource gets a
    rate. Instead of visiting every candidate (the others write 0 and
    leave the residuals unchanged), each trip jumps to the first sorted
    position whose resources all still have a positive residual, fills
    it, and clears every position sharing a resource the fill
    saturated. Trips = flows given a rate, and the rates are the full
    walk's, bit for bit. Returns ((F,) rates by flow id, trips)."""
    F = flist.shape[0]
    pos = jnp.arange(F, dtype=jnp.int32)
    ends = [idx[flist] for _, idx in resources]     # per sorted position
    alive = pos < n_cand
    for (avail, _), e in zip(resources, ends):
        alive &= avail[e] > 0

    def first(alive):
        return jnp.min(jnp.where(alive, pos, F))

    # a vmapped lane already done (i == F) still runs the body: its
    # indices clamp and the loop discards its results
    def body(s):
        i, n, alive, avails, wcf = s
        at = [e[i] for e in ends]
        r = functools.reduce(jnp.minimum,
                             [a[j] for a, j in zip(avails, at)])
        avails = tuple(a.at[j].add(-r) for a, j in zip(avails, at))
        # the fill saturates one of i's own resources, so i is cleared
        # below too; clearing it here bounds the trips by the candidates
        # whatever the residuals hold
        alive = alive.at[i].set(False)
        for a, e, j in zip(avails, ends, at):
            alive &= ~((e == j) & (a[j] <= 0))
        return (first(alive), n + 1, alive, avails,
                wcf.at[flist[i]].set(r))

    _, n_fill, _, _, wc_flow = jax.lax.while_loop(
        lambda s: s[0] < F, body,
        (first(alive), jnp.int32(0), alive,
         tuple(a for a, _ in resources), jnp.zeros((F,), jnp.float32)))
    return wc_flow, n_fill


@functools.partial(jax.jit,
                   static_argnames=("cp", "kernel", "wc_fill"))
def schedule_tick(state: CoordState, batch: CoflowBatch, now: jax.Array,
                  *, cp: CoordParams, kernel: str | None = None,
                  flows: FlowView | None = None,
                  wc_fill: str = "greedy"):
    """One Fig. 7 coordinator tick. Returns (new_state, out) with
    per-coflow equal rates (MADD), admission mask, queue, contention, and
    (when a FlowView is supplied) per-flow work-conservation rates."""
    return tick_core(state, batch, now, DynCoordParams.from_cp(cp),
                     kernel=kernel, flows=flows, wc_fill=wc_fill)


def tick_core(state: CoordState, batch: CoflowBatch, now: jax.Array,
              dp: DynCoordParams, *, kernel: str | None = None,
              flows: FlowView | None = None, wc_fill: str = "greedy"):
    """The Fig. 7 tick with fully traced parameters (un-jitted; callers
    embed it in their own jit/scan/vmap — fabric.jax_engine scans it)."""
    th = dp.thresholds
    C, P = batch.cnt_s.shape
    act = batch.active

    with jax.named_scope(SCOPE_QUEUES):
        # D3: per-flow thresholds (Eq. 1) — compare m_c * N_c against
        # Q_q^hi; the Fig. 10 A/N ablation (per_flow=0) uses Aalo
        # total-bytes queues
        qval = batch.m * batch.width.astype(jnp.float32)
        if batch.total is not None:
            qval = jnp.where(dp.per_flow > 0, qval, batch.total)
        q = _queue_of(qval, th)
        # §4.3 cluster dynamics: a coflow with both finished and live
        # flows re-queues by its estimated remaining length (the
        # caller-computed finished-flow-median m_hat, Eq. 1 form) —
        # approximate SRTF that can move a coflow back UP the queues,
        # matching Saath._assign_queues.
        if batch.mixed is not None:
            q_dyn = _queue_of(
                batch.m_dyn * batch.width.astype(jnp.float32), th)
            use_dyn = (dp.requeue > 0) & batch.mixed & act
            if dp.clairvoyant is not None:
                # mixed-mode dispatch: only clairvoyant rows may read the
                # exact-size median estimate
                use_dyn = use_dyn & (dp.clairvoyant > 0)
            q = jnp.where(use_dyn, q_dyn, q)
        if batch.s_mixed is not None:
            # learned-mode §4.3: re-queue from the pilot-sampling
            # estimate. Compiled in only when some row runs
            # non-clairvoyant; the clairvoyant gate keeps known-size rows
            # bit-identical inside a mixed vmap/stacked dispatch.
            q_smp = _queue_of(
                batch.s_m * batch.width.astype(jnp.float32), th)
            cl = (dp.clairvoyant if dp.clairvoyant is not None
                  else jnp.float32(1.0))
            q = jnp.where(
                (cl <= 0) & (dp.requeue > 0) & batch.s_mixed & act,
                q_smp, q)
        q = jnp.where(act, q, jnp.maximum(state.queue, 0))

        # D5: FIFO-derived deadlines, refreshed on queue entry (spans are
        # precomputed host-side in DynCoordParams, matching
        # core.queues.min_queue_residence).
        entered = act & (q != state.queue)
        K = th.shape[0]
        cq = jnp.zeros((K,), jnp.float32).at[q].add(
            act.astype(jnp.float32))
        t_min = dp.span[q] / (jnp.maximum(batch.width, 1) * dp.bw_ref)
        deadline = jnp.where(
            entered,
            now + dp.deadline_factor * jnp.maximum(cq[q], 1.0) * t_min,
            state.deadline)
        expired = act & (now >= deadline)

    # LCoF contention (Pallas kernel on TPU)
    with jax.named_scope(SCOPE_CONTENTION):
        k = ops.contention((batch.cnt_s > 0).astype(jnp.float32),
                           (batch.cnt_r > 0).astype(jnp.float32),
                           act, force=kernel)

    with jax.named_scope(SCOPE_ORDER):
        # order: expired first (by deadline — a float lexsort operand,
        # zero for everyone else), then (queue, k, stability, arrival);
        # coflows with no live ports and inactive coflows last, so perm's
        # first `n_live` entries double as the admission processing
        # list. jnp.lexsort: last key is primary.
        hp = act & ((batch.cnt_s > 0).any(axis=1)
                    | (batch.cnt_r > 0).any(axis=1))
        arr_rank = batch.arrival
        not_running = (~state.running).astype(jnp.int32)
        primary = jnp.where(~hp, 2, jnp.where(expired, 0, 1))
        dl_key = jnp.where(expired & hp, deadline, 0.0)
        # lcof=0 (Fig. 10 A/N): FIFO within queue — contention and
        # stability keys drop out, leaving (queue, arrival) exactly as
        # the reference
        lc = dp.lcof > 0
        key_q = jnp.where(expired, 0, q)
        key_k = jnp.where(expired | ~lc, 0, k)
        key_st = jnp.where(expired | ~lc, 0, not_running)
        # arr_rank stays a live key for EXPIRED coflows too: exact f32
        # deadline ties (same tick, same queue, same width) must break by
        # a layout-independent total order — the final arange(C)
        # tie-break is the slab POSITION, which differs between an
        # offline pack (cid order) and a session slab (submission
        # order), and would fork an otherwise bitwise-identical
        # incremental replay.
        perm = jnp.lexsort((jnp.arange(C), arr_rank, key_st, key_k,
                            key_q, dl_key, primary))

    with jax.named_scope(SCOPE_ADMIT):
        # D1/D2: all-or-none admission with MADD equal rates, processed
        # in `perm` priority order. Only a coflow with live ports can
        # change the carry (a missed or port-less coflow leaves `avail`
        # untouched), so the sequential pass runs as a while_loop over
        # the COMPACTED live list: trip count = live coflows, not padded
        # C. Results are identical to a full scan over perm — skipped
        # entries are no-ops — and the fleet engine's per-tick cost
        # drops with occupancy.
        min_rate = dp.min_rate_frac * dp.bw_ref
        cnt = jnp.concatenate([batch.cnt_s, batch.cnt_r], axis=1)  # (C, 2P)
        avail0 = jnp.concatenate([batch.bw_s, batch.bw_r])         # (2P,)
        if batch.cnt_x is not None:
            # leaf-spine: the MADD min also runs over the coflow's
            # uplink/downlink counts — same arithmetic, a wider concat
            cnt = jnp.concatenate([cnt, batch.cnt_x], axis=1)  # (C, 2P+Lx)
            avail0 = jnp.concatenate([avail0, batch.bw_x])
        has = cnt > 0
        inv = jnp.where(has, 1.0 / jnp.maximum(cnt, 1e-9), 0.0)
        bigm = jnp.where(has, 0.0, BIG)
        clist = perm                      # live coflows lead (see above)
        n_live = hp.sum().astype(jnp.int32)
        zC = jnp.zeros((C,), jnp.float32)

        def admit_body(s):
            k, avail, rate_, adm = s
            c = clist[k]
            r = (avail * inv[c] + bigm[c]).min()
            ok = (r >= min_rate) & (r < BIG)
            r = jnp.where(ok, r, 0.0)
            return (k + 1, avail - r * cnt[c], rate_.at[c].set(r),
                    adm.at[c].set(ok))

        _, avail, rate, admitted = jax.lax.while_loop(
            lambda s: s[0] < n_live, admit_body,
            (jnp.int32(0), avail0, zC, jnp.zeros((C,), bool)))

    # D4 work conservation over the missed list (lines 18-23), gated by
    # dp.wc via the candidate count (zero iterations when the switch is
    # off). `n_cand` counts the candidates offered to the fill (0 for
    # the max-min fill, which has no serial loop), `n_fill` those it
    # gave a rate.
    wc_on = dp.wc > 0
    if flows is None:
        # coflow-granular fallback: one equal rate across all live flows
        # of each missed coflow (the faithful collective-coflow mapping)
        def wc_body(s):
            j, avail_, wc = s
            c = clist[j]
            r = (avail_ * inv[c] + bigm[c]).min()
            ok = ~admitted[c] & (r > 0) & (r < BIG)
            r = jnp.where(ok, r, 0.0)
            return (j + 1, avail_ - r * cnt[c], wc.at[c].set(r))

        with jax.named_scope(SCOPE_WC_FILL):
            n_cand = jnp.where(wc_on, n_live, 0)
            _, _, wc_rate = jax.lax.while_loop(
                lambda s: s[0] < n_cand, wc_body,
                (jnp.int32(0), avail, zC))
            n_fill = (wc_rate > 0).sum().astype(jnp.int32)
        wc_flow = None
    else:
        # per-flow greedy fill, the reference's greedy_flow_alloc: live
        # flows of missed coflows, ordered by (coflow priority, flow
        # index) — exactly the reference's wc_order — each take
        # min(avail_src, avail_dst), so a strict SUBSET of a missed
        # coflow's flows can be rescued. One lexsort compacts the
        # candidates to the front; `_greedy_fill` then jumps from one
        # that can still get a rate to the next. Each rate saturates a
        # port (or link) for good, so the loop runs once per flow given
        # a rate, at most 2P (+ 2L) trips however many candidates
        # there are (none when the wc switch is off).
        wc_rate = zC
        avail_s, avail_r = avail[:P], avail[P:2 * P]
        F = flows.src.shape[0]
        with jax.named_scope(SCOPE_WC_ORDER):
            missed_c = hp & ~admitted
            cand0 = flows.live & missed_c[flows.cid] & wc_on
        if wc_fill == "maxmin":
            # max-min fair water-filling over the leftover flows (the
            # in-network allocation family), via the shared
            # `kernels.ops.maxmin_rates` backend — Pallas on TPU (or
            # force='interpret'/'pallas' through `kernel`), jnp
            # progressive filling otherwise. Incidence rows stack ports
            # then uplinks/downlinks; the sentinel leaf id Lf one-hots
            # to a zero column, so intra-leaf flows see ports only.
            with jax.named_scope(SCOPE_WC_FILL):
                a_send = jax.nn.one_hot(flows.src, P, axis=0,
                                        dtype=jnp.float32)
                a_recv = jax.nn.one_hot(flows.dst, P, axis=0,
                                        dtype=jnp.float32)
                bw_s_ext, bw_r_ext = avail_s, avail_r
                if flows.up is not None:
                    Lf = batch.cnt_x.shape[1] // 2
                    a_send = jnp.concatenate(
                        [a_send, jax.nn.one_hot(flows.up, Lf, axis=0,
                                                dtype=jnp.float32)])
                    a_recv = jnp.concatenate(
                        [a_recv, jax.nn.one_hot(flows.dn, Lf, axis=0,
                                                dtype=jnp.float32)])
                    bw_s_ext = jnp.concatenate(
                        [avail_s, avail[2 * P:2 * P + Lf]])
                    bw_r_ext = jnp.concatenate(
                        [avail_r, avail[2 * P + Lf:]])
                wc_flow = ops.maxmin_rates(
                    a_send, a_recv, cand0, bw_s_ext, bw_r_ext,
                    force=kernel)
                wc_flow = jnp.where(cand0, wc_flow, 0.0)
            n_cand = n_fill = jnp.int32(0)
        else:
            with jax.named_scope(SCOPE_WC_ORDER):
                invp = jnp.argsort(perm)  # priority rank of each coflow
                # three separate sort keys (candidates first, coflow
                # priority, flow index) — a fused invp[cid]*F + i key
                # would overflow int32 near the advertised 4k x 256k
                # scale
                flist = jnp.lexsort((jnp.arange(F), invp[flows.cid],
                                     (~cand0).astype(jnp.int32)))
                n_cand = cand0.sum().astype(jnp.int32)

            resources = [(avail_s, flows.src), (avail_r, flows.dst)]
            if flows.up is not None:
                # leaf-spine: the fill is also capped by the flow's
                # uplink/downlink residuals. Sentinel leaf id Lf
                # indexes a BIG extra slot, so intra-leaf flows are
                # never link-capped (and the slot absorbs their
                # subtracts harmlessly).
                Lf = batch.cnt_x.shape[1] // 2
                resources += [
                    (jnp.concatenate([avail[2 * P:2 * P + Lf],
                                      BIG[None]]), flows.up),
                    (jnp.concatenate([avail[2 * P + Lf:], BIG[None]]),
                     flows.dn)]
            with jax.named_scope(SCOPE_WC_FILL):
                wc_flow, n_fill = _greedy_fill(flist, n_cand, resources)

    new_state = CoordState(queue=jnp.where(act, q, state.queue),
                           deadline=deadline, running=admitted)
    # n_live / n_cand / n_fill: the admission loop's trips, the
    # work-conservation fill's candidates and the ones it gave a rate
    # this tick (the work counters `jax_engine` sums per row)
    out = {"rate": rate, "wc_rate": wc_rate, "wc_flow": wc_flow,
           "admitted": admitted, "queue": q, "contention": k,
           "expired": expired, "order": perm,
           "n_live": n_live, "n_cand": n_cand, "n_fill": n_fill}
    return new_state, out
