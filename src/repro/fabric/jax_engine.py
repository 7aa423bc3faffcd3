"""Batched fixed-step XLA fleet simulator (the tentpole of PR 1).

Where `fabric.engine.Simulator` replays ONE trace through a Python
event loop, this module replays a whole fleet: `core.jax_coordinator.
tick_core` is wrapped in a `jax.lax.scan` over δ-grid ticks and
`jax.vmap`-ed over a leading trace axis, so N traces (and, via stacked
`EngineParams`, M parameter settings) run as one XLA computation.

Semantics (DESIGN.md §3): a fixed-step simulation on the δ grid — the
schedule takes effect only at δ ticks, exactly the paper's pipelined
coordinator. Between the discrete events the event-driven reference
jumps across (arrival, flow completion, queue-threshold crossing,
starvation deadline) the Fig. 7 schedule is a deterministic function
of unchanged state, so each scan step safely jumps to the next
grid-quantized event; flow completion instants are still recorded
exactly (rates are constant inside an interval, the completion time
is algebraic). A flow finishing mid-interval leaves its bandwidth
idle until the next tick, matching the reference's δ-sensitivity
(Fig. 14(c)).

Full fidelity vs the numpy `Saath` reference (shared with
`policies.saath_jax`): work conservation runs at FLOW granularity (the
reference's greedy_flow_alloc order) and the §4.3 dynamics re-queue is
modelled exactly (per-coflow finished-flow median via the
host-precomputed size-sorted segment layout, `TraceBatch.perm_size`).
Equivalence is property-tested to 1% in tests/test_jax_engine.py on the
full reference configuration.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import jax_coordinator as jc
from repro.core.params import SchedulerParams
from repro.traces.batch import TraceBatch, pack

# completion slop: a flow whose remaining bytes are within REL_EPS of
# what this tick delivers completes now — f32 cannot resolve finer
# (accumulated over thousands of ticks), and without it a completion can
# slip a tick and desynchronize the replay from the float64 reference.
REL_EPS = 1e-5

# `jax.named_scope`s of the session loop and of the tick's engine-side
# parts (the coordinator's own parts are scoped in `jax_coordinator`)
SCOPE_SESSION = "saath.session"
SCOPE_VIEWS = "saath.tick.views"
SCOPE_HORIZON = "saath.tick.horizon"


class EngineParams(NamedTuple):
    """Traced scheduler knobs: a DynCoordParams plus the δ grid step.

    Every leaf may carry a leading sweep axis (see `simulate_sweep`) —
    including the dp.wc / dp.requeue mechanism switches, so those
    ablation grids vmap instead of recompiling. dp.lcof / dp.per_flow
    are traced too but need the ablation event-horizon structure
    compiled in (`_tick`'s with_ablations), which `simulate_batch`
    derives per call; `simulate_sweep` always runs full-SAATH queues.
    """
    dp: jc.DynCoordParams
    delta: jax.Array      # () f32 seconds

    @staticmethod
    def from_scheduler(p: SchedulerParams, *,
                       work_conservation: "bool | None" = None,
                       dynamics_requeue: "bool | None" = None,
                       lcof: bool = True,
                       per_flow_threshold: bool = True,
                       clairvoyant: "bool | None" = None) -> "EngineParams":
        cp = jc.CoordParams.from_params(p)
        cp = cp._replace(
            work_conservation=(cp.work_conservation
                               if work_conservation is None
                               else work_conservation),
            dynamics_requeue=(cp.dynamics_requeue
                              if dynamics_requeue is None
                              else dynamics_requeue),
            lcof=lcof, per_flow_threshold=per_flow_threshold,
            clairvoyant=(cp.clairvoyant if clairvoyant is None
                         else clairvoyant))
        return EngineParams(jc.DynCoordParams.from_cp(cp),
                            jnp.float32(p.delta))


class EngineState(NamedTuple):
    """Per-trace scan carry (all leaves get a leading batch axis).

    The four trailing fields exist only in SESSION states (online
    `repro.api` slabs; `None` — compiled out — for offline replays):
    they carry the *pending event horizon* of a schedule interval that
    an advance's `n_end` cap truncated, so the next advance resumes the
    STORED rates from the STORED anchor instead of re-evaluating the
    boundary tick — the same discipline the numpy session oracle uses,
    which is what makes incremental replay bitwise-equal to the offline
    scan (re-evaluation is a fixed point only until §4.3 dynamics drift
    moves a queue). Integration is anchor-based: every capped piece of
    the interval recomputes `sent`/`fct` from (pend_tick, pend_sent),
    so splitting an interval at arbitrary horizons cannot change a
    single f32 rounding versus the offline one-shot integration.
    """
    coord: jc.CoordState
    sent: jax.Array      # (F,) f32 bytes
    done: jax.Array      # (F,) bool
    fct: jax.Array       # (F,) f32 absolute completion time (0 until done)
    finished: jax.Array  # (C,) bool
    cct: jax.Array       # (C,) f32 completion - arrival (nan until done)
    t0: jax.Array        # () f32 grid origin (0; kept for generality)
    tick: jax.Array      # () i32 next tick index
    rate: Optional[jax.Array] = None       # (F,) f32 pending rates
    pend_sent: Optional[jax.Array] = None  # (F,) f32 sent at the anchor
    pend_tick: Optional[jax.Array] = None  # () f32 anchor tick index
    pend_next: Optional[jax.Array] = None  # () f32 horizon tick (0=none)


class EngineResult(NamedTuple):
    cct: np.ndarray       # (B, C) nan for unfinished/padded coflows
    fct: np.ndarray       # (B, F) nan for unfinished/padded flows
    sent: np.ndarray      # (B, F) bytes
    finished: np.ndarray  # (B, C) bool (padded coflows report True)
    ticks: int            # max δ-grid ticks simulated across the batch
    events: int           # event steps (scan iterations) executed

    @property
    def avg_cct(self) -> np.ndarray:
        """(B,) mean CCT per trace over its real coflows.

        A row with no finished real coflows (e.g. an all-padding session
        slab row) reports NaN — the "nothing completed" value of the
        `repro.api.Result` normalizer — instead of tripping numpy's
        all-NaN RuntimeWarning.
        """
        from repro.fabric.metrics import nan_row_mean

        return nan_row_mean(self.cct)


class WorkCounts(NamedTuple):
    """Per-row work counters of one session dispatch (each (B,) int32,
    or folded (shards, B/shards) on the pmap path), accumulated in the
    session while_loop's carry:

    - `event_steps`: the loop's iterations (every row of a slab, or of a
      shard, pays each one);
    - `lane_steps`: iterations in which the row's lane was open (short
      of its horizon with unfinished coflows);
    - `admit_trips`: admission while_loop trips (live coflows per tick);
    - `wc_trips`: candidates offered to the work-conservation fill
      (candidate flows per tick for the per-flow greedy fill, live
      coflows for the coflow-granular one, 0 for the max-min fill);
    - `wc_fills`: the candidates the fill gave a rate (the per-flow
      greedy fill's loop trips, at most 2P (+ 2L) a tick; 0 for the
      max-min fill)."""
    event_steps: jax.Array
    lane_steps: jax.Array
    admit_trips: jax.Array
    wc_trips: jax.Array
    wc_fills: jax.Array


# ---- single-trace tick ---------------------------------------------------

def _init_state(tb: TraceBatch, ep: EngineParams) -> EngineState:
    """Single-trace state init (arrays here are unbatched rows).

    The δ grid is pinned at t=0 for every replay — the same grid the
    online sessions use — so an incremental session replay and the
    offline scan see bit-identical `now` values at every tick (an
    arrival-quantized origin would shift the f32 rounding of
    `t0 + tick*δ`). Idle ticks before the first arrival cost nothing:
    the arrival event horizon jumps straight across them.
    """
    F = tb.cid.shape[0]
    C = tb.arrival.shape[0]
    t0 = jnp.float32(0.0)
    return EngineState(
        coord=jc.CoordState(jnp.full((C,), -1, jnp.int32),
                            jnp.full((C,), jnp.inf, jnp.float32),
                            jnp.zeros((C,), bool)),
        sent=jnp.zeros((F,), jnp.float32),
        done=~tb.flow_valid,
        fct=jnp.zeros((F,), jnp.float32),
        finished=~tb.coflow_valid,
        cct=jnp.full((C,), jnp.nan, jnp.float32),
        t0=t0, tick=jnp.int32(0))


# max ticks one event-jump may skip (idle gaps between arrivals are
# jumped exactly; this only caps pathological/finished lanes)
MAX_JUMP_TICKS = 1024.0
# an idle lane (no live flows) jumps straight to its next arrival in
# one step; this only caps that jump inside the f32-exact tick range
IDLE_JUMP_TICKS = float(1 << 22)
# with the §4.3 dynamics re-queue active the cap MIRRORS
# fabric.engine.Simulator's default max_jump of 200δ — semantic, not
# just a guard: the estimated remaining length drifts continuously (no
# discrete event), so both replay loops must re-invoke the coordinator
# at the same bounded cadence or their queue moves (and hence
# trajectories) fork. Between discrete events a re-evaluation on
# unchanged state is a fixed point, so matching the reference's cadence
# costs steps, never correctness.
DYNAMICS_JUMP_TICKS = 200.0


def _segment_sum(data: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Sum `data` (F,) over contiguous index ranges [lo, hi) (any shape
    of lo/hi) via one cumsum + two boundary gathers."""
    s = jnp.concatenate([jnp.zeros_like(data[:1]), jnp.cumsum(data)])
    return s[hi] - s[lo]


def _segment_max(data: jax.Array, tb: TraceBatch) -> jax.Array:
    """Max of non-negative `data` (F,) per contiguous coflow segment ->
    (C,). Segmented cummax via associative_scan; the value at the last
    flow of each segment is the segment max (0 for padded coflows)."""
    def comb(a, b):
        va, ia = a
        vb, ib = b
        return jnp.where(ia == ib, jnp.maximum(va, vb), vb), ib

    v, _ = jax.lax.associative_scan(comb, (data, tb.cid))
    return jnp.where(tb.coflow_valid, v[tb.flow_hi - 1], 0.0)


def _views(state: EngineState, tb: TraceBatch, now: jax.Array,
           eps_t: jax.Array, *, per_flow_wc: bool, with_dynamics: bool,
           with_ablations: bool, with_sampling: bool = False,
           active_gate: Optional[jax.Array] = None):
    """One tick's coordinator view of the slab: activation, per-(coflow,
    port) live counts, Eq. 1 m_c, and (when compiled in) the §4.3
    finished-flow-median inputs — shared by the scanned `_tick` and the
    single-shot session `plan_tick`.

    `active_gate` (sessions) is `tick < n_end`: a lane at or past its
    horizon has its whole step DISCARDED anyway (`_tick`'s no-op
    select), so deactivating it up front is free — and it zeroes the
    admission/work-conservation while_loop trip counts, making the
    trailing no-op ticks of a chunk cost almost nothing. That surplus
    is what lets one pooled dispatch amortize its fixed cost across
    many session lanes (DESIGN.md §8).
    """
    # activation (reference: arrival <= now + eps, eps << δ)
    active = tb.coflow_valid & ~state.finished & (tb.arrival <= now + eps_t)
    if active_gate is not None:
        active = active & active_gate
    live = active[tb.cid] & ~state.done & tb.flow_valid
    livef = live.astype(jnp.float32)

    # coordinator view of the fabric: m_c (Eq. 1) over ALL flows,
    # live-flow counts per (coflow, port) — scatter-free: 1-D cumsums
    # over the host-precomputed (cid, port)-sorted flow orders
    m = _segment_max(state.sent * tb.flow_valid, tb)
    cnt_s = _segment_sum(livef[tb.perm_src], tb.lo_src, tb.hi_src)
    cnt_r = _segment_sum(livef[tb.perm_dst], tb.lo_dst, tb.hi_dst)
    total = _segment_sum(state.sent * tb.flow_valid, tb.flow_lo,
                         tb.flow_hi) if with_ablations else None

    # leaf-spine fabric (DESIGN.md §11): per-(coflow, link) live counts
    # via the same host-precomputed sorted segment layout as the ports,
    # compiled out entirely (None) on a big-switch slab (Lf == 0)
    cnt_x = bw_x = link_up = link_dn = None
    Lf = tb.bw_up.shape[-1]
    if Lf:
        cnt_up = _segment_sum(livef[tb.perm_up], tb.lo_up, tb.hi_up)
        cnt_dn = _segment_sum(livef[tb.perm_dn], tb.lo_dn, tb.hi_dn)
        cnt_x = jnp.concatenate([cnt_up, cnt_dn], axis=1)  # (C, 2Lf)
        bw_x = jnp.concatenate([tb.bw_up, tb.bw_dn])       # (2Lf,)
        if per_flow_wc:
            link_up, link_dn = tb.link_up, tb.link_dn

    mixed = m_dyn = None
    if with_dynamics:
        # §4.3 remaining-length estimate: the EXACT median of finished-
        # flow sizes per coflow, as order statistics over the host-
        # precomputed (cid, size)-sorted segment layout (tb.perm_size) —
        # one cumsum of the done mask gives each done flow's rank inside
        # its segment, the two middle ranks select the median, no
        # per-tick sort or scatter.
        done_real = (state.done & tb.flow_valid).astype(jnp.float32)
        d_s = done_real[tb.perm_size]
        size_s = tb.size[tb.perm_size]
        cid_s = tb.cid[tb.perm_size]
        S = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                             jnp.cumsum(d_s)])
        n_done = (S[tb.flow_hi] - S[tb.flow_lo]).astype(jnp.int32)  # (C,)
        drank = (S[:-1] - S[tb.flow_lo][cid_s]).astype(jnp.int32)   # (F,)
        k1 = jnp.maximum(n_done - 1, 0) // 2
        k2 = n_done // 2
        hit1 = (d_s > 0.5) & (drank == k1[cid_s])
        hit2 = (d_s > 0.5) & (drank == k2[cid_s])

        # each hit mask selects AT MOST ONE flow per segment, so the
        # pick is a segmented MAX (exact for any padding/layout — a
        # cumsum-difference would round by ulp(prefix), making the
        # median depend on what else shares the slab row, which breaks
        # the session-vs-offline bitwise contract). perm_size permutes
        # flows only WITHIN coflow segments, so [flow_lo, flow_hi)
        # spans are valid in this order too.
        def pick(data):
            def comb(a, b):
                va, ia = a
                vb, ib = b
                return jnp.where(ia == ib, jnp.maximum(va, vb), vb), ib
            v, _ = jax.lax.associative_scan(comb, (data, cid_s))
            return jnp.where(tb.coflow_valid, v[tb.flow_hi - 1], 0.0)

        v1 = pick(size_s * hit1)
        v2 = pick(size_s * hit2)
        f_e = 0.5 * (v1 + v2)        # median (0 when nothing finished)
        rem_dyn = jnp.maximum(f_e[tb.cid] - state.sent, 0.0) * livef
        m_dyn = _segment_max(rem_dyn, tb)
        n_live_c = _segment_sum(livef, tb.flow_lo, tb.flow_hi)
        mixed = active & (n_done > 0) & (n_live_c > 0.5)

    s_mixed = s_m = None
    if with_sampling:
        # non-clairvoyant §4.3 inputs: the size estimate is the MEAN of
        # finished-PILOT sizes (a finished flow's size equals its
        # delivered bytes, so the estimate only ever reads observable
        # quantities); coflows whose pilots are all in flight are not
        # re-queue candidates and keep the bytes-sent Eq. 1 placement.
        if tb.pilot is None:
            raise ValueError("with_sampling needs a TraceBatch packed "
                             "with sampling=True (pilot layout missing)")
        pdone = (tb.pilot & tb.flow_valid & state.done).astype(jnp.float32)
        n_p = _segment_sum(pdone, tb.flow_lo, tb.flow_hi)       # (C,)
        p_sum = _segment_sum(pdone * tb.size, tb.flow_lo, tb.flow_hi)
        f_hat = p_sum / jnp.maximum(n_p, 1.0)
        rem_s = jnp.maximum(f_hat[tb.cid] - state.sent, 0.0) * livef
        s_m = _segment_max(rem_s, tb)
        n_live_s = _segment_sum(livef, tb.flow_lo, tb.flow_hi)
        s_mixed = active & (n_p > 0.5) & (n_live_s > 0.5)

    batch = jc.CoflowBatch(active=active, arrival=tb.arrival_rank, m=m,
                           width=tb.width, cnt_s=cnt_s, cnt_r=cnt_r,
                           bw_s=tb.bw_send, bw_r=tb.bw_recv,
                           total=total, mixed=mixed, m_dyn=m_dyn,
                           cnt_x=cnt_x, bw_x=bw_x,
                           s_mixed=s_mixed, s_m=s_m)
    flows = jc.FlowView(cid=tb.cid, src=tb.src, dst=tb.dst, live=live,
                        up=link_up, dn=link_dn) \
        if per_flow_wc else None
    return batch, flows, active, live, livef


def _tick(state: EngineState, tb: TraceBatch, ep: EngineParams,
          kernel: Optional[str], *, per_flow_wc: bool = True,
          with_dynamics: bool = True,
          with_ablations: bool = False,
          wc_maxmin: bool = False,
          with_sampling: bool = False,
          n_end: Optional[jax.Array] = None):
    """Advance one *event step*: schedule at the current δ tick, find the
    next instant the schedule could change (arrival, flow completion,
    queue-threshold crossing, starvation deadline — the reference
    simulator's event list), quantize it UP to the δ grid, and integrate
    the constant rates across the jumped interval. Between those events
    the Fig. 7 schedule is a fixed point of unchanged state, so skipping
    the intermediate ticks reproduces the per-tick trajectory exactly.

    The three keyword flags are STATIC structure switches (resolved
    host-side, not traced): `per_flow_wc` selects the exact per-flow
    work-conservation fill vs the cheaper coflow-granular one,
    `with_dynamics` builds the §4.3 finished-flow-median machinery, and
    `with_ablations` builds the total-bytes queue inputs/events for the
    Fig. 10 per_flow_threshold=0 path. Turning one off removes its cost
    from the compiled step entirely.

    `n_end` (traced, sessions only) caps the replay at tick index
    `n_end`: the jump never passes it, and once `tick >= n_end` the step
    is an exact no-op (the whole new state is discarded), so an online
    `SaathSession` can advance to a wall-clock horizon, accept new
    arrivals, and re-enter the scan without ever having scheduled a tick
    that couldn't yet see them. When the cap truncates a schedule
    interval, the pending event horizon (rates + anchor) is carried in
    the state, and the next step RESUMES the stored schedule — stopping
    early only at a since-submitted arrival's tick, exactly like the
    numpy session oracle — instead of re-evaluating the boundary tick,
    so incremental replay is bitwise the offline scan. `None` (offline
    replay) compiles both the cap and the pending machinery out.

    Returns (new state, (admission trips, work-conservation candidates,
    work-conservation fills)) of the step's coordinator tick
    (`tick_core`'s work counts).
    """
    session = n_end is not None
    delta = ep.delta
    tickf = state.tick.astype(jnp.float32)
    now = state.t0 + tickf * delta
    eps_t = 1e-3 * delta
    can = tickf < n_end if session else None
    with jax.named_scope(SCOPE_VIEWS):
        batch, flows, active, live, livef = _views(
            state, tb, now, eps_t, per_flow_wc=per_flow_wc,
            with_dynamics=with_dynamics, with_ablations=with_ablations,
            with_sampling=with_sampling, active_gate=can)
    total = batch.total
    coord, out = jc.tick_core(state.coord, batch, now, ep.dp,
                              kernel=kernel, flows=flows,
                              wc_fill="maxmin" if wc_maxmin else "greedy")
    trips = (out["n_live"], out["n_cand"], out["n_fill"])
    with jax.named_scope(SCOPE_HORIZON):
        # per-flow rates: MADD equal rate for admitted coflows + the work-
        # conservation fill (flow-granular when per_flow_wc, else the
        # coflow-granular equal rate; both already gated by dp.wc)
        r_f = out["rate"][tb.cid] * livef
        if per_flow_wc:
            r_f = r_f + out["wc_flow"]
        else:
            r_f = r_f + out["wc_rate"][tb.cid] * livef
        served = live & (r_f > 0)
        rem = tb.size - state.sent

        # ---- event horizon (mirrors Simulator._next_event + Saath
        # progress_events, vectorized) -------------------------------------
        inf = jnp.float32(jnp.inf)
        t_fin = jnp.min(jnp.where(served, now + rem / jnp.maximum(r_f, 1e-30),
                                  inf))
        # queue-threshold crossing, per the active threshold rule: flow f of
        # coflow c crosses when sent_f reaches Q_q^hi / N_c (Eq. 1), or —
        # for the per_flow=0 Aalo-queue ablation — when the coflow's TOTAL
        # bytes reach Q_q^hi (q = the post-assignment queue)
        q = jnp.maximum(coord.queue, 0)
        thq = ep.dp.thresholds[q]
        lim = (thq / jnp.maximum(tb.width, 1).astype(jnp.float32))[tb.cid]
        dt_th = jnp.where(served & jnp.isfinite(lim) & (lim > state.sent),
                          (lim - state.sent) / jnp.maximum(r_f, 1e-30), inf)
        t_th = now + jnp.min(dt_th)
        if with_ablations:
            R_c = _segment_sum(r_f, tb.flow_lo, tb.flow_hi)
            dt_tot = jnp.where(active & (R_c > 0) & jnp.isfinite(thq)
                               & (thq > total),
                               (thq - total) / jnp.maximum(R_c, 1e-30), inf)
            t_th = now + jnp.where(ep.dp.per_flow > 0, jnp.min(dt_th),
                                   jnp.min(dt_tot))
        t_dl = jnp.min(jnp.where(active & (coord.deadline > now + eps_t),
                                 coord.deadline, inf))
        t_arr = jnp.min(jnp.where(tb.coflow_valid & (tb.arrival > now + eps_t),
                                  tb.arrival, inf))
        t_ev = jnp.minimum(jnp.minimum(t_fin, t_th), jnp.minimum(t_dl, t_arr))
        # the pilot-sampling estimate drifts continuously too (rem = f_hat -
        # sent), so learned mode needs the same bounded re-evaluation
        # cadence as the §4.3 exact-median machinery
        jump = DYNAMICS_JUMP_TICKS if (with_dynamics or with_sampling) \
            else MAX_JUMP_TICKS
        n_ev = jnp.where(jnp.isfinite(t_ev),
                         jnp.ceil((t_ev - state.t0) / delta - 1e-4),
                         tickf + jump)
        # the jump cap bounds RE-EVALUATION cadence on live state (§4.3
        # drift; pathological-lane guard). With nothing live there is
        # nothing to re-evaluate — an idle gap (e.g. the run-up from the
        # t=0 grid origin to a late first arrival) is jumped in ONE step,
        # bounded only by the f32-exact tick range.
        idle_jump = jnp.float32(IDLE_JUMP_TICKS)
        hi = tickf + jnp.where(jnp.any(live), jnp.float32(jump), idle_jump)
        n_un = jnp.clip(n_ev, tickf + 1.0, hi)  # uncapped horizon

        if not session:
            n_next = n_un
            r_use, anchor_t, anchor_tick = r_f, now, tickf
            anchor_sent, coord_new = state.sent, coord
        else:
            cap = jnp.maximum(n_end, tickf + 1.0)
            # pending-horizon resume: if the previous advance capped a
            # schedule interval, keep integrating the STORED rates from the
            # STORED anchor to the stored horizon — or to the δ-quantized
            # tick of an arrival submitted since the anchor (a discrete
            # event the offline loop would have stopped at) — instead of
            # re-evaluating the boundary tick.
            pend_t = state.t0 + state.pend_tick * delta
            late = jnp.min(jnp.where(
                tb.coflow_valid & (tb.arrival > pend_t + eps_t),
                tb.arrival, inf))
            late_n = jnp.maximum(jnp.ceil((late - state.t0) / delta - 1e-4),
                                 state.pend_tick + 1.0)
            stop = jnp.minimum(state.pend_next, late_n)
            resuming = (state.pend_next > tickf) & (stop > tickf)
            n_next = jnp.where(resuming, jnp.minimum(stop, cap),
                               jnp.minimum(n_un, cap))
            r_use = jnp.where(resuming, state.rate, r_f)
            anchor_t = jnp.where(resuming, pend_t, now)
            anchor_tick = jnp.where(resuming, state.pend_tick, tickf)
            anchor_sent = jnp.where(resuming, state.pend_sent, state.sent)
            # a resumed interval does NOT re-invoke the coordinator: queue
            # moves / deadline refreshes happen only at evaluation instants,
            # exactly as in the offline loop
            coord_new = jax.tree_util.tree_map(
                lambda a, b: jnp.where(resuming, a, b), state.coord, coord)
            served = live & (r_use > 0)

        # ---- integrate the constant rates across the interval, ANCHORED
        # at the evaluation instant: sent/fct are recomputed from the
        # anchor, so an interval split by n_end caps integrates to exactly
        # the same f32 values as the offline single-shot step -------------
        dt = (n_next - anchor_tick) * delta
        rem_a = tb.size - anchor_sent
        adv = r_use * dt
        fin = served & (adv >= rem_a - REL_EPS * tb.size)
        fct = jnp.where(fin, anchor_t + rem_a / jnp.maximum(r_use, 1e-30),
                        state.fct)
        sent = jnp.where(fin, tb.size,
                         jnp.minimum(tb.size, anchor_sent + adv))
        done = state.done | fin

        # coflow completions: CCT = last FCT - arrival (fct is 0 until a
        # flow completes, so the masked row-max sees only completed flows)
        undone = _segment_sum((tb.flow_valid & ~done).astype(jnp.float32),
                              tb.flow_lo, tb.flow_hi)
        newly = active & (undone < 0.5)
        last_fct = _segment_max(fct * tb.flow_valid, tb)
        cct = jnp.where(newly, last_fct - tb.arrival, state.cct)

        if not session:
            return EngineState(coord=coord, sent=sent, done=done, fct=fct,
                               finished=state.finished | newly, cct=cct,
                               t0=state.t0,
                               tick=state.tick + (n_next - tickf)
                               .astype(jnp.int32)), trips
        # pending bookkeeping: cleared once the interval's horizon (or the
        # arrival stop) is reached; (re)armed when this step's interval was
        # truncated by the n_end cap. The anchor leaves (rate/pend_sent/
        # pend_tick) always reflect the interval just integrated, so a
        # re-armed pending resumes from the original evaluation instant.
        hit = n_next >= jnp.where(resuming, stop, n_un)
        pend_next = jnp.where(hit, jnp.float32(0.0),
                              jnp.where(resuming, state.pend_next, n_un))
        new = EngineState(coord=coord_new, sent=sent, done=done, fct=fct,
                          finished=state.finished | newly, cct=cct,
                          t0=state.t0, tick=state.tick + (n_next - tickf)
                          .astype(jnp.int32),
                          rate=r_use, pend_sent=anchor_sent,
                          pend_tick=anchor_tick, pend_next=pend_next)
        # at/past the horizon the step must be a PURE no-op: the schedule at
        # tick n_end is evaluated on the NEXT advance, when every arrival
        # submitted at <= n_end*δ is already in the slab — evaluating it now
        # would bake deadlines/queues that ignore those arrivals. (`can`
        # also pre-gated activation above, so this discarded step computed
        # with zero admission/WC loop trips.)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(can, a, b), new, state), trips


# ---- batched chunk runner ------------------------------------------------

def _norm_features(features: tuple) -> tuple:
    """Pad a legacy short features tuple to the full 5-slot form
    `(per_flow_wc, with_dynamics, with_ablations, wc_maxmin,
    with_sampling)` — later slots default off, so pre-existing 4-tuple
    (and pool-padded 3-tuple) callers keep their exact structure."""
    f = tuple(features)
    if not 1 <= len(f) <= 5:
        raise ValueError(f"features tuple of length {len(f)}")
    return f + (False,) * (5 - len(f))


@functools.partial(jax.jit, static_argnames=(
    "chunk", "kernel", "sweep", "features"))
def _run_chunk(state: EngineState, tb: TraceBatch, ep: EngineParams,
               *, chunk: int, kernel: Optional[str], sweep: bool,
               features: tuple) -> EngineState:
    """Scan `chunk` ticks for every trace in the batch (one executable,
    reused across chunks so the host completion loop never recompiles).
    sweep=True maps the EngineParams' leading axis alongside the traces.
    `features` = (per_flow_wc, with_dynamics, with_ablations,
    wc_maxmin, with_sampling), the static structure switches threaded to
    `_tick`. Offline replays
    only: sessions go through `_run_session_block`, whose device-side
    while_loop carries the per-row horizon caps.
    """
    (per_flow_wc, with_dynamics, with_ablations, wc_maxmin,
     with_sampling) = _norm_features(features)
    ep_ax = 0 if sweep else None

    def scan_ticks(s, tb_row, ep_row):
        def body(c, _):
            c, _ = _tick(c, tb_row, ep_row, kernel,
                         per_flow_wc=per_flow_wc,
                         with_dynamics=with_dynamics,
                         with_ablations=with_ablations,
                         wc_maxmin=wc_maxmin,
                         with_sampling=with_sampling)
            return c, None
        s, _ = jax.lax.scan(body, s, None, length=chunk)
        return s

    return jax.vmap(scan_ticks, in_axes=(0, 0, ep_ax))(state, tb, ep)


@functools.partial(jax.jit, static_argnames=("sweep",))
def _init_batch(tb: TraceBatch, ep: EngineParams, *,
                sweep: bool) -> EngineState:
    return jax.vmap(_init_state, in_axes=(0, 0 if sweep else None))(tb, ep)


def default_max_ticks(tb: TraceBatch, delta: float, slack: float = 4.0,
                      ) -> int:
    """Sound-ish horizon bound: at every tick at least the head-of-line
    coflow progresses at its bottleneck rate, so the makespan is at most
    last_arrival + sum of per-coflow bottleneck times (x slack for
    deadline/WC interleavings and idle arrival gaps)."""
    bw = np.where(tb.bw_send > 0, tb.bw_send, np.inf).min()
    per_port = np.zeros((tb.num_traces, 2, tb.num_ports))
    np.add.at(per_port, (np.arange(tb.num_traces)[:, None], 0, tb.src),
              tb.size * tb.flow_valid)
    np.add.at(per_port, (np.arange(tb.num_traces)[:, None], 1, tb.dst),
              tb.size * tb.flow_valid)
    serial = per_port.max(axis=(1, 2)) / bw  # per-trace, coarse
    Lf = tb.bw_up.shape[-1]
    if Lf:
        # oversubscribed uplinks/downlinks can be the bottleneck: fold
        # in each link's bytes over its capacity (sentinel Lf = no link)
        per_link = np.zeros((tb.num_traces, 2, Lf + 1))
        rows = np.arange(tb.num_traces)[:, None]
        np.add.at(per_link, (rows, 0, tb.link_up), tb.size * tb.flow_valid)
        np.add.at(per_link, (rows, 1, tb.link_dn), tb.size * tb.flow_valid)
        cap = np.stack([tb.bw_up, tb.bw_dn], axis=1)  # (B, 2, Lf)
        t_link = np.where(cap > 0, per_link[:, :, :Lf] / np.maximum(
            cap, 1e-30), 0.0).max(axis=(1, 2))
        serial = np.maximum(serial, t_link)
    last = np.where(tb.coflow_valid, tb.arrival, 0.0).max(axis=1)
    # bottleneck-sum bound per trace: sum of each coflow's own bottleneck
    tot = np.einsum("bf->b", tb.size * tb.flow_valid) / bw
    horizon = float((last + slack * np.maximum(serial, tot)).max())
    return max(int(np.ceil(horizon / delta)) + 2, 8)


def resolve_kernel(kernel: Optional[str],
                   use_pallas: bool) -> Optional[str]:
    """`use_pallas=True` opts the tick's inner ops (LCoF contention, the
    max-min water-filling fill) into the Pallas kernels, and an
    out-of-domain shape then raises rather than running the reference
    (`repro.kernels.ops`). On TPU that is the compiled kernels. Off TPU
    it is `interpret` mode — the kernel BODY executed on CPU, slow, and
    there only so the CPU tests can check the kernels' parity. An
    explicit `kernel` force always wins; default (False) keeps backend
    auto-dispatch."""
    if kernel is not None or not use_pallas:
        return kernel
    from repro.kernels.ops import _on_tpu

    return "pallas" if _on_tpu() else "interpret"


def simulate_batch(traces: "Sequence | TraceBatch",
                   params: Optional[SchedulerParams] = None, *,
                   max_ticks: Optional[int] = None, chunk: int = 128,
                   kernel: Optional[str] = None,
                   work_conservation: "bool | None" = None,
                   dynamics_requeue: "bool | None" = None,
                   lcof: bool = True,
                   per_flow_threshold: bool = True,
                   clairvoyant: "bool | None" = None,
                   fidelity: str = "flow",
                   topology=None,
                   use_pallas: bool = False) -> EngineResult:
    """Replay a fleet of traces under one parameter setting.

    Internal engine entry point: the public front door is
    `repro.api.run(Scenario(..., engine="jax"))`, which owns result
    normalization and the engine-equivalence contract. Only
    `repro.api` and the engine's own tests call this directly.

    The mechanism switches default to the SchedulerParams fields
    (work_conservation / dynamics_requeue) or full SAATH (lcof /
    per_flow_threshold); pass explicit values for Fig. 10 ablations.
    `fidelity` picks the work-conservation granularity: "flow" (default)
    is the paper-exact per-flow greedy fill; "coflow" hands leftover
    bandwidth to a missed coflow as ONE equal rate — the faithful
    mapping for collective coflows (a partial issue is meaningless) and
    the throughput mode for large parameter sweeps (~3x cheaper steps).
    Runs jitted `chunk`-tick scans until every coflow of every trace
    has finished (or `max_ticks` is exhausted, which raises — mirroring
    the reference simulator's max_steps guard).
    """
    params = params or SchedulerParams()
    kernel = resolve_kernel(kernel, use_pallas)
    features = features_for(
        params, fidelity=fidelity, work_conservation=work_conservation,
        dynamics_requeue=dynamics_requeue, lcof=lcof,
        per_flow_threshold=per_flow_threshold, topology=topology,
        clairvoyant=clairvoyant)
    with_sampling = features[4]
    tb = traces if isinstance(traces, TraceBatch) else \
        pack(traces, port_bw=params.port_bw, topology=topology,
             sampling=with_sampling, pilot_frac=params.pilot_frac)
    if with_sampling and tb.pilot is None:
        raise ValueError("non-clairvoyant replay needs a TraceBatch "
                         "packed with sampling=True")
    ep = EngineParams.from_scheduler(
        params, work_conservation=work_conservation,
        dynamics_requeue=dynamics_requeue, lcof=lcof,
        per_flow_threshold=per_flow_threshold, clairvoyant=clairvoyant)
    return _drive(tb, ep, params.delta, max_ticks, chunk, kernel,
                  sweep=False, features=features)


def simulate_sweep(trace, params_list: Sequence[SchedulerParams], *,
                   max_ticks: Optional[int] = None, chunk: int = 128,
                   kernel: Optional[str] = None,
                   fidelity: str = "flow",
                   topology=None,
                   use_pallas: bool = False) -> EngineResult:
    """Replay ONE trace under M parameter settings as one computation.

    Internal engine entry point: the public front door is
    `repro.api.run(Scenario(..., sweep=...))`.

    All settings must share num_queues (K is a static shape) and delta
    is taken per-setting — the scan length covers the smallest δ. The
    work-conservation / §4.3-re-queue switches are traced leaves, so
    settings may mix them freely (the dynamics machinery is compiled in
    when ANY setting re-queues). Returns an EngineResult whose leading
    axis is the setting axis.
    """
    if fidelity not in ("flow", "coflow"):
        raise ValueError(f"unknown fidelity {fidelity!r}")
    k = {len(p.thresholds()) for p in params_list}
    if len(k) != 1:
        raise ValueError("sweep settings must share num_queues")
    if len({p.port_bw for p in params_list}) != 1:
        # port bandwidths are baked into the packed TraceBatch, so a
        # per-setting bw would silently run every lane on settings[0]'s
        raise ValueError("sweep settings must share port_bw")
    kernel = resolve_kernel(kernel, use_pallas)
    sampling_any = any(not p.clairvoyant for p in params_list)
    if sampling_any and len({p.pilot_frac for p in params_list}) > 1:
        # the pilot layout is baked into the packed row, which the
        # sweep repeats — per-setting pilot fractions would need
        # per-row re-packing
        raise ValueError("sweep settings must share pilot_frac")
    tb1 = pack([trace], port_bw=params_list[0].port_bw,
               topology=topology, sampling=sampling_any,
               pilot_frac=params_list[0].pilot_frac)
    B = len(params_list)
    tb = TraceBatch(*(None if a is None else np.repeat(a, B, axis=0)
                      for a in tb1))
    eps = [EngineParams.from_scheduler(p) for p in params_list]
    if sampling_any:
        # dp.clairvoyant must be an ARRAY leaf on every row for the
        # stack below (1.0 = clairvoyant row inside the mixed sweep)
        eps = [e if e.dp.clairvoyant is not None else
               e._replace(dp=e.dp._replace(clairvoyant=jnp.float32(1.0)))
               for e in eps]
    ep = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *eps)
    min_delta = min(p.delta for p in params_list)
    features = (fidelity == "flow",
                any(p.dynamics_requeue and p.clairvoyant
                    for p in params_list), False,
                getattr(topology, "wc_fill", "greedy") == "maxmin",
                any(p.dynamics_requeue and not p.clairvoyant
                    for p in params_list))
    return _drive(tb, ep, min_delta, max_ticks, chunk, kernel, sweep=True,
                  features=features)


def _drive(tb: TraceBatch, ep: EngineParams, delta: float,
           max_ticks: Optional[int], chunk: int, kernel: Optional[str],
           *, sweep: bool, features: tuple) -> EngineResult:
    if max_ticks is None:
        max_ticks = default_max_ticks(tb, delta)
    state = _init_batch(tb, ep, sweep=sweep)
    events = 0
    # every event step advances >= 1 grid tick, so max_ticks also bounds
    # the number of event steps a terminating replay can need
    while events < max_ticks:
        state = _run_chunk(state, tb, ep, chunk=chunk, kernel=kernel,
                           sweep=sweep, features=features)
        events += chunk
        if bool(jnp.all(state.finished)):
            break
    else:
        raise RuntimeError(
            f"jax_engine: {int((~np.asarray(state.finished)).sum())} "
            f"coflows unfinished after {events} event steps "
            f"(raise max_ticks or check the trace)")
    fct = np.asarray(state.fct, np.float64)
    fct[~np.asarray(state.done)] = np.nan
    fct[~tb.flow_valid] = np.nan
    return EngineResult(cct=np.asarray(state.cct, np.float64),
                        fct=fct,
                        sent=np.asarray(state.sent, np.float64),
                        finished=np.asarray(state.finished),
                        ticks=int(np.asarray(state.tick).max()),
                        events=events)


# ---- online session support (repro.api.SaathSession) ---------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_rows(tree, idx, rows):
    """Write stacked row updates into a device-resident slab pytree.

    `tree` is any leading-axis-batched pytree (a `TraceBatch` or a
    session `EngineState`), `idx` a (k,) int array of row indices and
    `rows` a structurally-identical pytree whose leaves carry the k
    updated rows stacked on axis 0. Passing a PLAIN tuple of trees
    with matching tuples of idx/rows scatters them all in ONE fused
    dispatch (the `SessionPool` updates its TraceBatch and EngineState
    together this way). This is the pool's dirty-row upload path: only
    the rows whose membership/state changed cross the host-device
    boundary; clean rows never re-upload (DESIGN.md §8). The input
    tree is DONATED — XLA updates the slab buffers in place, so a
    scatter costs O(dirty rows), not O(slab); callers must rebind."""
    if type(tree) is tuple:       # NamedTuple slabs are leaves-bearing
        return tuple(
            jax.tree_util.tree_map(
                lambda a, u, i=i: a.at[i].set(u), t, r)
            for t, i, r in zip(tree, idx, rows))
    return jax.tree_util.tree_map(lambda a, u: a.at[idx].set(u),
                                  tree, rows)


@jax.jit
def gather_rows(tree, idx: jax.Array):
    """Slice rows `idx` out of a device-resident slab pytree (stacked on
    axis 0) — the download half of the `SessionPool` row contract: the
    host mirrors only the rows a caller actually inspects."""
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def features_for(params: SchedulerParams, *, fidelity: str = "flow",
                 work_conservation: "bool | None" = None,
                 dynamics_requeue: "bool | None" = None,
                 lcof: bool = True,
                 per_flow_threshold: bool = True,
                 topology=None,
                 clairvoyant: "bool | None" = None) -> tuple:
    """The static `(per_flow_wc, with_dynamics, with_ablations,
    wc_maxmin, with_sampling)` structure switches `_tick` compiles
    against, derived
    exactly as `simulate_batch` derives them — shared with the online
    session so an incremental replay runs the same compiled step
    structure. `wc_maxmin` comes from the topology's `wc_fill` knob
    (LeafSpine only); the big switch always greedy-fills. The §4.3
    re-queue splits by clairvoyance: `with_dynamics` builds the exact
    finished-flow-median machinery (known sizes), `with_sampling` the
    pilot-estimate machinery (learned sizes)."""
    if fidelity not in ("flow", "coflow"):
        raise ValueError(f"unknown fidelity {fidelity!r}")
    dyn = (params.dynamics_requeue if dynamics_requeue is None
           else dynamics_requeue)
    cl = params.clairvoyant if clairvoyant is None else clairvoyant
    return (fidelity == "flow",
            dyn and cl,
            not (lcof and per_flow_threshold),
            getattr(topology, "wc_fill", "greedy") == "maxmin",
            dyn and not cl)


def _session_while(state: EngineState, tb: TraceBatch, ep: EngineParams,
                   n_end: jax.Array, max_steps: jax.Array,
                   counts: Optional[WorkCounts], *,
                   kernel: Optional[str], features: tuple):
    """The session while_loop body shared by the single-slab and the
    pmap (sharded) dispatch paths: vmapped `_tick` steps until every
    lane of THIS slab (or shard) has reached its horizon or finished
    all its real coflows. The loop condition is local to the rows it
    sees, so under `pmap` each device terminates independently — a
    shard whose lanes drain early stops stepping without waiting on
    its neighbors. `counts` (per-row `WorkCounts`; zeros when None)
    is the carry's starting value: the loop adds this dispatch's work
    to it. Returns (state, steps, counts)."""
    (per_flow_wc, with_dynamics, with_ablations, wc_maxmin,
     with_sampling) = _norm_features(features)
    zero = jnp.zeros(n_end.shape, jnp.int32)
    init = WorkCounts(*[zero] * len(WorkCounts._fields)) \
        if counts is None else counts

    def lane_open(s):
        tickf = s.tick.astype(jnp.float32)
        return ~((tickf >= n_end) | jnp.all(s.finished, axis=-1))

    def cond(carry):
        s, steps, _ = carry
        return jnp.any(lane_open(s)) & (steps < max_steps)

    def body(carry):
        s, steps, c = carry
        opened = lane_open(s).astype(jnp.int32)
        s, (n_live, n_cand, n_fill) = jax.vmap(
            lambda srow, tbrow, nerow, eprow: _tick(
                srow, tbrow, eprow, kernel, per_flow_wc=per_flow_wc,
                with_dynamics=with_dynamics,
                with_ablations=with_ablations, wc_maxmin=wc_maxmin,
                with_sampling=with_sampling, n_end=nerow))(
                    s, tb, n_end, ep)
        c = WorkCounts(c.event_steps + 1, c.lane_steps + opened,
                       c.admit_trips + n_live, c.wc_trips + n_cand,
                       c.wc_fills + n_fill)
        return s, steps + 1, c

    with jax.named_scope(SCOPE_SESSION):
        return jax.lax.while_loop(cond, body,
                                  (state, jnp.int32(0), init))


@functools.partial(jax.jit, static_argnames=("kernel", "features"))
def _run_session_block(state: EngineState, tb: TraceBatch,
                       ep: EngineParams, n_end: jax.Array,
                       max_steps: jax.Array,
                       counts: Optional[WorkCounts] = None, *,
                       kernel: Optional[str], features: tuple):
    """Advance every session lane to its own `n_end` horizon (or until
    its real coflows finish) in ONE dispatch: a device-side while_loop
    over vmapped `_tick` steps runs EXACTLY the event steps the fleet
    needs — no fixed-chunk padding, no host round-trip per chunk. This
    is what makes a pooled advance cost one dispatch's fixed overhead
    for the whole fleet instead of per session (DESIGN.md §8).

    `ep` carries a leading ROW axis on every leaf (the `SessionPool`
    stacks one `EngineParams` per slab row), so a heterogeneous
    multi-tenant fleet — per-row thresholds, δ, deadline factors,
    traced mechanism switches — still rides one while_loop dispatch."""
    return _session_while(state, tb, ep, n_end, max_steps, counts,
                          kernel=kernel, features=features)


def row_mesh(shards: int):
    """A 1-D `Mesh` over the first `shards` devices, axis name "rows" —
    the row-axis partitioning the sharded `SessionPool` slab lives on.
    CPU runs get multiple host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    jax initializes; see `make pool-sharded` / the CI sharded step)."""
    devs = jax.devices()
    if shards < 1:
        raise ValueError("shards must be >= 1")
    if shards > len(devs):
        raise ValueError(
            f"shards={shards} needs {shards} devices but jax sees "
            f"{len(devs)}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={shards} (or more) "
            f"before the first jax import")
    return jax.sharding.Mesh(np.array(devs[:shards]), ("rows",))


@functools.lru_cache(maxsize=None)
def _pmapped_session_block(kernel: Optional[str], features: tuple,
                           mesh) -> "object":
    """The multi-device dispatch path, one compiled program per
    (kernel, features, mesh): `pmap` maps the SHARD axis of a folded
    ``(shards, rows_per_shard, ...)`` slab onto the mesh's devices, and
    every device runs its OWN `_session_while` loop over its rows.
    Rows are independent sessions — there is no cross-shard
    communication — so `pmap` is the right tool: each device's program
    is EXACTLY the single-slab while_loop (no GSPMD partitioner, hence
    no partitioner-inserted collectives; a collective inside loops
    with per-shard trip counts would deadlock the CPU backend), shards
    advance concurrently, and each terminates independently. The
    per-row arithmetic is the same vmapped `_tick` as the single-slab
    path, which is what keeps an N-shard pool bitwise-identical to a
    1-shard pool (tests/test_pool_sharded.py)."""
    devices = list(np.asarray(mesh.devices).flat)

    def block(state, tb, ep, n_end, max_steps, counts=None):
        return _session_while(state, tb, ep, n_end, max_steps, counts,
                              kernel=kernel, features=features)

    return jax.pmap(block, axis_name="rows",
                    in_axes=(0, 0, 0, 0, None, 0), devices=devices)


def session_advance(state: EngineState, tb: TraceBatch, ep: EngineParams,
                    *, n_end, chunk: int = 32,
                    kernel: Optional[str] = None,
                    features: tuple = (True, True, False, False, False),
                    max_steps: int = 10_000_000, mesh=None,
                    block: bool = True,
                    counts: Optional[WorkCounts] = None):
    """Re-enter the jitted tick loop on a live session slab until every
    lane has reached its δ-grid tick target or finished all its real
    coflows. `n_end` is a scalar or a (B,) per-row array — a
    `SessionPool` advances a whole fleet of sessions, each to its own
    horizon, with ONE dispatch; lanes already at their horizon are
    exact no-ops. `ep` must carry a leading (B,) row axis on every
    leaf (stack identical rows for a homogeneous fleet): each tenant
    row schedules under its OWN thresholds/δ/mechanism switches inside
    the one dispatch. The caps are traced, so one compiled executable
    serves every advance of every session. `chunk` is accepted for API
    compatibility but unused: the device-side while_loop runs exactly
    the event steps needed.

    `mesh` (a `row_mesh`) routes the dispatch through the pmap path:
    the caller hands the slab in FOLDED layout — every leaf reshaped
    ``(B, ...) -> (shards, B // shards, ...)`` with shard i resident
    on mesh device i — and each device runs its own while_loop over
    its rows. `block=False` (the async dispatch mode) skips the
    host-side step-count readback entirely — the dispatch is enqueued
    and the DEVICE step counter is returned for the caller to fold
    into its lazy control mirror — so the caller can chain the next
    advance without waiting for this one's results.

    `counts` (`WorkCounts` shaped like `state.tick`; zeros when None)
    starts the per-row work counters, so a chain of dispatches handing
    each one the last one's counters accumulates them on the device
    (a second compiled variant: the first link of a chain passes None).
    Returns (state, event_steps, counts): `event_steps` an int when
    blocking, the device counter otherwise; `counts` stays on the
    device."""
    del chunk
    ne = np.asarray(n_end, np.float32)
    if ne.shape != state.tick.shape:
        ne = np.broadcast_to(
            ne.reshape(-1) if ne.ndim else ne,
            (int(np.prod(state.tick.shape)),)).reshape(state.tick.shape)
    ne = jnp.asarray(ne.copy(), jnp.float32)
    if mesh is not None:
        fn = _pmapped_session_block(kernel, tuple(features), mesh)
        state, steps, counts = fn(state, tb, ep, ne, jnp.int32(max_steps),
                                  counts)
    else:
        state, steps, counts = _run_session_block(
            state, tb, ep, ne, jnp.int32(max_steps), counts,
            kernel=kernel, features=features)
    if not block:
        return state, steps, counts
    steps = int(np.asarray(steps).max())  # saath: lint-ok(host-pull-unaccounted): blocking mode's sanctioned sync; pool accounts the ctl read
    if steps >= max_steps:
        raise RuntimeError(
            f"session_advance exceeded {max_steps} event steps before "
            f"reaching its tick horizon (check the slab)")
    return state, steps, counts


@functools.partial(jax.jit, static_argnames=("kernel", "features"))
def session_plan_tick(state: EngineState, tb: TraceBatch,
                      ep: EngineParams, *, kernel: Optional[str] = None,
                      features: tuple = (True, False, False, False, False),
                      row_mask: Optional[jax.Array] = None):
    """One coordinator tick on the slab WITHOUT integrating rates: the
    wave-planning mode `runtime.coflow_bridge.plan_waves` uses (a wave =
    the admitted set of one tick; the caller completes admitted coflows
    instantly). `row_mask` (B,) selects which sessions of a pooled slab
    plan this tick — unselected rows are exact no-ops (their state is
    untouched and they admit nothing). Any pending capped interval of a
    planning row is discarded: planning re-evaluates every tick.
    `ep` carries a leading (B,) row axis (per-tenant parameters, like
    `session_advance`). Returns (state with post-tick coordinator
    carry and tick+1, admitted (B, C) bool)."""
    (per_flow_wc, with_dynamics, with_ablations, wc_maxmin,
     with_sampling) = _norm_features(features)

    def one(s, tb_row, m, ep_row):
        tickf = s.tick.astype(jnp.float32)
        now = s.t0 + tickf * ep_row.delta
        eps_t = 1e-3 * ep_row.delta
        batch, flows, _, _, _ = _views(
            s, tb_row, now, eps_t, per_flow_wc=per_flow_wc,
            with_dynamics=with_dynamics, with_ablations=with_ablations,
            with_sampling=with_sampling)
        coord, out = jc.tick_core(
            s.coord, batch, now, ep_row.dp, kernel=kernel, flows=flows,
            wc_fill="maxmin" if wc_maxmin else "greedy")
        new = s._replace(coord=coord, tick=s.tick + 1)
        if s.pend_next is not None:
            new = new._replace(pend_next=jnp.zeros_like(s.pend_next))
        new = jax.tree_util.tree_map(
            lambda a, b: jnp.where(m, a, b), new, s)
        return new, out["admitted"] & m

    mask = row_mask if row_mask is not None else \
        jnp.ones(state.tick.shape, bool)
    return jax.vmap(one)(state, tb, mask, ep)


__all__ = ["EngineParams", "EngineState", "EngineResult", "WorkCounts",
           "default_max_ticks", "features_for", "resolve_kernel",
           "session_advance", "session_plan_tick", "scatter_rows",
           "gather_rows"]
