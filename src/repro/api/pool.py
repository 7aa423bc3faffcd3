"""Multi-tenant serving plane: a fleet of `SaathSession`s on ONE slab.

A `SessionPool` hosts up to `max_sessions` concurrent online sessions
as ROWS of a single leading-axis-batched `TraceBatch` slab, so one
dispatch of the jitted `fabric.jax_engine` tick scan advances every
tenant's coordinator at once (`jax.vmap` over the row axis) instead of
N sequential scans over N private slabs. This is the paper's global
coordinator serving many tenants (PAPER.md §5 / Table 2 is about
per-decision coordinator cost under load): the marginal cost of an
extra tenant is one more vmapped lane, not one more compiled replica.

Ownership (DESIGN.md §8):

* the POOL owns the device-facing slab, and since ISSUE 5 the
  authoritative `TraceBatch` + `EngineState` leaves LIVE ON DEVICE
  between dispatches. Membership/state changes (`submit`, `poll`
  retirement, `release`, `complete`) mark rows dirty, and `_ensure`
  applies them as DIRTY-ROW SCATTER updates (`jax_engine.scatter_rows`
  over host-staged `traces.batch.pack_row` rows) — a clean row never
  re-crosses the host-device boundary. Numpy mirrors survive only as
  the lazily-materialized debug/oracle view (`host_view()`) and the
  per-row host entries sessions carry;
* each `SaathSession` is a VIEW onto one pool row: it keeps the host
  truth for its tenant (live `_Entry`s, clock, δ-grid tick, epoch,
  pending-horizon mirror) and delegates every device interaction —
  `advance`, `plan_tick`, slab membership — to the pool. After a
  dispatch the row's host entries are STALE until someone looks
  (`poll`, `snapshot`, a re-pack): `_materialize` then gathers exactly
  the stale rows back (`jax_engine.gather_rows`) in one dispatch. A
  standalone `SaathSession(backend="jax")` is simply the row-0 view of
  a private single-row pool, so single-session code is the B=1 case of
  the same machinery.

Per-tenant scheduler parameters: every slab row carries its OWN
`EngineParams` (thresholds, δ, deadline factor, traced wc/requeue/
lcof/per-flow switches) — `session(params=..., mechanisms=...)` admits
a tenant under its own configuration, and the stacked (B,)-leaf
`EngineParams` rides the same single while_loop dispatch
(`jax_engine.session_advance` vmaps the parameter rows exactly like
`simulate_sweep` does for offline grids). The one compiled-shape
constraint is `num_queues` (K): all tenants must share the pool's K.
The STATIC structure switches (`features_for`) are OR-combined across
admitted rows, mirroring `simulate_sweep`'s "dynamics compiled in when
ANY setting re-queues" rule.

Rows advance to INDEPENDENT horizons: `jax_engine.session_advance`
takes a per-row `n_end`, and a lane at (or past) its horizon is an
exact no-op, so `pool.advance(dt)` moves every tenant together in one
dispatch chain while `session.advance(dt)` on a single view moves only
its row (the other lanes no-op). Per-session results are bitwise
identical to standalone sessions — padding never perturbs a row's
arithmetic (tests/test_pool.py, tests/test_pool_fuzz.py).

Long-horizon sessions re-base their δ-grid EPOCH on re-pack once the
row's relative tick exceeds ``REBASE_TICKS``: arrivals, deadlines, and
completion times are stored relative to the row epoch, so a session
that has been up for hours keeps full δ resolution in the f32 slab
(absolute times would lose the grid beyond ~1e6 ticks). The epoch is
strictly PER ROW — an old tenant re-basing never perturbs a young
neighbor's grid (tests/test_pool.py).

Sharded slab (ISSUE 6): ``SessionPool(..., shards=N)`` partitions the
row axis across N devices on a 1-D "rows" mesh
(`jax_engine.row_mesh`): the slab is kept in a FOLDED dispatch layout
— every leaf reshaped ``(B, ...) -> (N, B/N, ...)`` with shard i
resident on device i — and `session_advance` `pmap`s the shard axis,
so each device runs its OWN while_loop over its rows and terminates
independently (pmap compiles the exact single-slab program per
device — no GSPMD partitioner, hence no partitioner-inserted
collectives, which would deadlock divergent per-shard loops on the
CPU backend). The row scatters, gathers and planning ticks are
per-device programs too (`jax_engine.row_programs`, at shard-local
indices): no row leaves its device and the folded slab is never
reshaped on the device. Rows are independent sessions — there is no
cross-shard communication anywhere — so an N-shard pool is
bitwise-identical to the 1-shard pool (tests/test_pool_sharded.py,
tests/test_pool_fleet_sharded.py). CPU CI gets N host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Async double-buffered dispatch (ISSUE 6, default ON): `advance` ENQUEUES
the fleet dispatch and returns without downloading the tiny control
mirrors — the device (tick, finished) handles are parked as the
pool's deferred ctl and consumed lazily (`_sync_ctl`) at the next
poll / snapshot / re-pack / `host_view` point. Chained advances
overwrite the parked ctl, so a burst of K advances costs K dispatches
but ONE control download. This is safe because ticks only grow and a
lane at (or past) the horizon a dispatch hands it is an exact no-op:
a STALE tick mirror used as an untargeted row's horizon can only
UNDER-ask, never perturb. ``async_dispatch=False`` restores the
blocking per-dispatch download.

Opt-in pinned features: ``SessionPool(..., features=(pfw, dyn, abl))``
freezes the compiled structure switches up front, so a heterogeneous
tenant joining mid-flight NEVER recompiles the fleet executable —
admission validates that the tenant's required features are compiled
in (the same OR-superset rule `_ensure` applies dynamically: the
traced per-row parameter switches make compiled-in machinery
semantics-preserving for rows that don't use it).

`pool.io` counts every host-device crossing (row scatters/gathers,
full rebuild uploads, the tiny control reads — deferred, under async
dispatch, to the next sync point), which is how
`benchmarks/pool_throughput.py` proves clean-row advances upload
nothing.
"""
from __future__ import annotations

import bisect
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.sanitize import accounted_transfer
from repro.core.params import SchedulerParams

# re-base a row's grid epoch at the first re-pack past this relative
# tick: f32 keeps exact integers to 2^24 and δ-resolution sums well
# past 2^20, so re-basing at 2^20 leaves a 16x safety margin
REBASE_TICKS = 1 << 20
# hard per-dispatch cap on relative ticks — a single advance spanning
# more than this is split into epochs (each split re-packs and
# re-bases, so `tickf` arithmetic never leaves the f32-exact range)
MAX_REL_TICKS = 1 << 22

# host spans of the pool's work, on the profiler's clock (a profiler
# trace places them beside the device ops they wait on or feed)
SPAN_SYNC_CTL = "saath.pool.sync_ctl"   # host blocked on a dispatch
SPAN_GATHER = "saath.pool.gather"       # stale rows gathered and synced
SPAN_STAGE = "saath.pool.stage"         # numpy packing of rows to upload
SPAN_UPLOAD = "saath.pool.upload"       # row scatters, slab uploads
SPAN_DISPATCH = "saath.pool.dispatch"   # the session_advance enqueue


def _io_accounted(method):
    """Mark a SessionPool method as a SANCTIONED host-device crossing:
    its transfers are what the `pool.io` counters cover, so they run
    inside an `accounted_transfer` carve-out. Everything else the pool
    does is then provably transfer-free under
    `repro.analysis.sanitize.assert_no_transfers` — the sanitizer the
    pool suites arm around clean-row advances."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with accounted_transfer():
            return method(self, *args, **kwargs)
    return wrapper


def _tree_nbytes(tree) -> int:
    return int(sum(np.asarray(leaf).nbytes
                   for leaf in jax.tree_util.tree_leaves(tree)))


def _bucket(n: int) -> int:
    """The row count a scatter or gather of `n` rows is padded to: the
    next power of two, so a slab of B rows compiles at most
    log2(B) + 1 programs of each, all at build time."""
    return 1 << max(n - 1, 0).bit_length()


def _stack(trees):
    """Stack host row trees on a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *trees)


class PoolFullError(RuntimeError):
    """The pool is at its admission cap (`max_sessions` live rows).

    The ONE failure `CoflowServer.register` translates into an
    `AdmissionError`; any other pool/session fault propagates untouched
    (it is a bug or a bad configuration, not an admission decision)."""


class SessionPool:
    """An admission-capped fleet of jax-backend `SaathSession`s sharing
    one device-resident slab.

    All sessions share the pool's fabric size (`num_ports`), fidelity,
    and queue count K — one compiled tick structure serves the whole
    fleet — but each admitted tenant may bring its own
    `SchedulerParams`/mechanism switches (`session(params=...,
    mechanisms=...)`); rows without overrides run the pool defaults.
    `session()` admits a new tenant (raising when the pool is full);
    `release()` (or `SaathSession.close()`) frees the row for the next
    tenant.
    """

    def __init__(self, params: Optional[SchedulerParams] = None, *,
                 num_ports: int, max_sessions: int = 16,
                 mechanisms: Optional[dict] = None,
                 fidelity: str = "flow", kernel: Optional[str] = None,
                 chunk: int = 32, min_coflow_capacity: int = 16,
                 min_flow_capacity: int = 64, shards: int = 1,
                 async_dispatch: bool = True,
                 features: Optional[tuple] = None,
                 topology=None):
        from repro.fabric import jax_engine
        from repro.fabric.topology import (leaf_links_for,
                                           normalize_topology)

        self._je = jax_engine
        self.num_ports = int(num_ports)
        # fabric model, PINNED at construction like num_ports/K: the
        # link segment layout is part of the slab shape (Lf leaves) and
        # wc_maxmin is a compiled structure switch, so heterogeneous
        # topologies cannot share one slab without recompiles
        self.topology = normalize_topology(topology)
        self._Lf = leaf_links_for(self.topology, self.num_ports)
        self.kernel = kernel
        self.chunk = int(chunk)
        self.max_sessions = int(max_sessions)
        if self.max_sessions <= 0:
            raise ValueError("max_sessions must be positive")
        self._fidelity = fidelity
        self.shards = int(shards)
        if self.shards > 1:
            if self.max_sessions % self.shards:
                raise ValueError(
                    f"max_sessions ({self.max_sessions}) must be a "
                    f"multiple of shards ({self.shards}): the row axis "
                    f"is partitioned evenly across the mesh")
            self._mesh = jax_engine.row_mesh(self.shards)
            self._sharding = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec("rows"))
        else:
            if self.shards < 1:
                raise ValueError("shards must be >= 1")
            self._mesh = None
            self._sharding = None
        # the row scatter, gather and planning tick for this layout
        self._rows = jax_engine.row_programs(self._mesh)
        self._async = bool(async_dispatch)
        if features is not None and len(features) == 3:
            # pre-topology callers pinned (pfw, dyn, abl); the fabric
            # fill switch rides the pool's own topology
            features = tuple(features) + (
                getattr(self.topology, "wc_fill", "greedy") == "maxmin",)
        if features is not None and len(features) == 4:
            # pre-sampling callers pinned (pfw, dyn, abl, maxmin):
            # every tenant was clairvoyant, so sampling stays out
            features = tuple(features) + (False,)
        if features is not None and (len(features) != 5
                                     or not all(isinstance(b, (bool,
                                                               np.bool_))
                                                for b in features)):
            raise ValueError(
                "features must be a 5-tuple of bools (per_flow_wc, "
                "with_dynamics, with_ablations, wc_maxmin, "
                "with_sampling)")
        self._pinned = tuple(bool(b) for b in features) \
            if features is not None else None

        self.params, self._ep, self._base_features = \
            self._resolve(params or SchedulerParams(), mechanisms)

        self._C_cap = int(min_coflow_capacity)
        self._F_cap = int(min_flow_capacity)
        self._sessions: List[Optional["object"]] = \
            [None] * self.max_sessions
        self._free = list(range(self.max_sessions))
        self._blank_rows: set = set()
        # TraceBatch and EngineState, DEVICE leaves (authoritative). A
        # sharded pool stores both in DISPATCH LAYOUT — folded (shards,
        # B/shards, ...) with shard i on device i — which the pmap
        # dispatch and the per-device row scatters and gathers consume
        # and produce as they are: no reshape on the device, ever
        self._tb = None
        self._state = None
        self._ep_disp = None   # folded copy of _ep_stack (sharded pool)
        self._scratch = None   # 1-row numpy TraceBatch packing stage
        # tiny host control mirrors, refreshed from each dispatch's
        # status download: per-row relative tick (the no-op horizon for
        # unworked rows) and per-coflow finished flags (so poll only
        # gathers rows that completed something new)
        self._ticks = None     # (B,) np.int32
        self._fin = None       # (B, C) np.bool_
        # per-row scheduler parameters (stacked at dispatch time)
        self._row_ep = [self._ep] * self.max_sessions
        self._row_feat = [self._base_features] * self.max_sessions
        self._ep_stack = None          # stacked (B,)-leaf EngineParams
        self._features_now = self._pinned or self._base_features
        # pilot leaf compiled into the slab? (with_sampling): the
        # TraceBatch STRUCTURE differs, so a flip is a rebuild-class
        # event — pinned pools never flip (admission validates)
        self._sampling = bool(self._features_now[4])
        # async dispatch chain: the parked device ctl handles of the
        # most recent dispatch, plus the rows awaiting its download
        # (tick_dev, fin_dev, work counters, dispatch number) | None
        self._ctl = None
        self._pend_rows: dict = {}     # row -> (session, global n_end)
        # sessions whose `_new_done` is set: the O(1) index behind the
        # completion bitmap, so a poll over a clean fleet never walks
        # the roster (B per-session polls per step must not cost B^2)
        self._fresh: set = set()
        # host<->device transfer accounting (benchmarks assert on this)
        # and the dispatches' work counters (`jax_engine.WorkCounts`,
        # added at each ctl download): event steps of the longest device
        # loop, and per-row sums of open-lane steps, admission trips,
        # serial work-conservation candidates and the fills among them,
        # and max-min fill candidates; `scatter_rows` counts the rows
        # the bucketed row scatters wrote, `pad_rows` the no-op pads
        # among them
        self.io = dict(full_uploads=0, row_uploads=0, row_downloads=0,
                       upload_bytes=0, download_bytes=0, ctl_bytes=0,
                       dispatches=0, event_steps=0, lane_steps=0,
                       admit_trips=0, wc_trips=0, wc_fills=0,
                       maxmin_cands=0, scatter_rows=0, pad_rows=0)

    def _resolve(self, params: Optional[SchedulerParams],
                 mechanisms: Optional[dict]) -> tuple:
        """Validate one tenant's (params, mechanisms) against the pool's
        compiled structure; returns (params, EngineParams, features)."""
        from repro.api.scenario import check_mechanisms

        mech = check_mechanisms(mechanisms)
        p = (params or self.params).with_mechanisms(mech)
        if hasattr(self, "params") and \
                p.num_queues != self.params.num_queues:
            raise ValueError(
                f"per-tenant params must share the pool's num_queues "
                f"(K={self.params.num_queues} is a compiled shape); "
                f"got K={p.num_queues}")
        lcof = mech.get("lcof", True)
        per_flow = mech.get("per_flow_threshold", True)
        ep = self._je.EngineParams.from_scheduler(
            p, lcof=lcof, per_flow_threshold=per_flow)
        feat = self._je.features_for(
            p, fidelity=self._fidelity, lcof=lcof,
            per_flow_threshold=per_flow, topology=self.topology)
        if self._pinned is not None:
            names = ("per_flow_wc", "with_dynamics", "with_ablations",
                     "wc_maxmin", "with_sampling")
            for i, name in enumerate(names):
                if feat[i] and not self._pinned[i]:
                    raise ValueError(
                        f"tenant needs compiled feature {name!r} but "
                        f"the pool pinned features={self._pinned} at "
                        f"construction; pin a superset (pinning is "
                        f"what keeps admission recompile-free)")
            if not p.clairvoyant and not self._pinned[4]:
                # a learned-mode tenant carries a traced clairvoyant
                # leaf in its EngineParams row — admitting one into a
                # pool compiled without sampling would change the
                # stacked-parameter structure (a recompile)
                raise ValueError(
                    "non-clairvoyant tenant needs compiled feature "
                    f"'with_sampling' but the pool pinned features="
                    f"{self._pinned} at construction; pin a superset")
        return p, ep, feat

    # ---- admission -------------------------------------------------------

    @property
    def num_sessions(self) -> int:
        return self.max_sessions - len(self._free)

    @property
    def sessions(self) -> list:
        return [s for s in self._sessions if s is not None]

    @_io_accounted
    def session(self, params: Optional[SchedulerParams] = None,
                mechanisms: Optional[dict] = None):
        """Admit a new tenant session — with its OWN scheduler
        parameters/mechanism switches when given (pool defaults
        otherwise); raises `PoolFullError` (a `RuntimeError`) when the
        pool is at its admission cap."""
        from repro.api.session import SaathSession

        if not self._free:
            raise PoolFullError(
                f"SessionPool is full ({self.max_sessions} sessions); "
                f"release one (or raise max_sessions) to admit more")
        p, ep, feat = self._resolve(params, mechanisms)
        # admission commits the tenant's EngineParams row to device —
        # a sanctioned crossing, counted like every other upload
        self.io["upload_bytes"] += _tree_nbytes(ep)
        row = self._free.pop(0)
        sess = SaathSession(p, num_ports=self.num_ports,
                            backend="jax", kernel=self.kernel,
                            chunk=self.chunk, topology=self.topology,
                            _pool=self, _row=row)
        self._sessions[row] = sess
        self._blank_rows.discard(row)
        self._row_ep[row] = ep
        self._row_feat[row] = feat
        self._ep_stack = None
        return sess

    def release(self, sess) -> None:
        """Free a session's row (dropping any unfinished coflows); the
        row is recycled for the next admitted tenant."""
        row = sess._row
        if row is None or self._sessions[row] is not sess:
            raise ValueError("session does not belong to this pool")
        self._sessions[row] = None
        self._blank_rows.add(row)
        bisect.insort(self._free, row)
        sess._row = None
        sess._pool = None
        sess._host_stale = False
        sess._new_done = False
        sess._host_done = False
        self._fresh.discard(sess)
        # any parked ctl entry for the freed row is disarmed by the
        # session-identity check in `_sync_ctl` (the row re-blanks — a
        # scatter, which syncs first — before its next reuse)
        self._row_ep[row] = self._ep
        self._row_feat[row] = self._base_features
        self._ep_stack = None

    def _adopt(self, sess) -> None:
        """Bind an externally-constructed standalone session as row 0
        of this (private, single-row) pool."""
        assert self.max_sessions == 1 and self._free == [0]
        self._free.clear()
        self._sessions[0] = sess

    # ---- fleet stepping --------------------------------------------------

    def advance(self, dt: float) -> float:
        """Move EVERY admitted session's clock by `dt` seconds and
        schedule all their δ-grid ticks with one vmapped dispatch chain
        (each row on its own δ grid); returns the (common) elapsed
        fleet time."""
        if dt < 0:
            raise ValueError("advance(dt) needs dt >= 0")
        targets = []
        for s in self.sessions:
            s._clock += float(dt)
            targets.append(
                (s, int(math.floor(s._clock / s.params.delta + 1e-9))))
        self._advance(targets)
        return float(dt)

    def poll(self) -> List[Tuple[object, object]]:
        """Completed-since-last-poll coflows across the fleet, as
        (session, CompletedCoflow) pairs."""
        self._materialize(completions_only=True)
        out = []
        for s in self.sessions:
            out.extend((s, d) for d in s.poll())
        return out

    def completed_sessions(self) -> list:
        """The fleet's NEW-COMPLETION BITMAP, as the sessions it names:
        rows whose last dispatch finished something not yet drained by
        a poll, plus rows with host-side force-completes
        (`SaathSession.complete`). This is the harvest index the
        `CoflowServer` advance loop walks — a clean tenant costs ZERO
        host work per fleet step (no per-session `poll()` probe). A
        sync point of the async dispatch contract (consumes the
        deferred ctl download)."""
        self._sync_ctl()
        return [s for s in self.sessions
                if s._new_done or s._host_done]

    # ---- slab machinery (the device-facing half of the row-view
    # contract; sessions call these with themselves as the row) --------

    def _target_tick(self, s) -> int:
        """The session's effective tick target: its last synced tick,
        or the horizon of a still-parked async dispatch (whichever is
        later) — the skip test must not re-dispatch a row already
        enqueued to (or past) the asked-for horizon."""
        pend = self._pend_rows.get(s._row)
        if pend is not None and pend[0] is s:
            return max(s._tick, pend[1])
        return s._tick

    @_io_accounted
    def _advance(self, targets) -> None:
        """Advance the given (session, global n_end) targets; sessions
        not listed keep their row at its current tick (exact no-ops in
        the dispatch)."""
        work = {}
        for s, n_end in targets:
            if n_end <= self._target_tick(s):
                continue
            if not s._live:
                # nothing on the row: the grid is advanced host-side
                s._tick = n_end
                continue
            work[s._row] = (s, n_end)
        if not work:
            return
        if self._async and all(n_end - s._epoch <= MAX_REL_TICKS
                               for s, n_end in work.values()):
            self._dispatch_async(work)
            return
        # blocking path: giant horizon jumps need the MAX_REL_TICKS
        # split loop (each leg re-packs and re-bases the epoch), whose
        # decisions read the fresh ctl — flush any parked one first
        self._sync_ctl()
        while work:
            self._ensure()
            ne = self._ticks.astype(np.float32)
            for r, (s, n_end) in work.items():
                ne[r] = min(n_end, s._epoch + MAX_REL_TICKS) - s._epoch
            tb, ep = self._dispatch_slab()
            n = self.io["dispatches"] + 1
            with TraceAnnotation(SPAN_DISPATCH, dispatch=n):
                state, _, counts = self._je.session_advance(
                    self._state, tb, ep, n_end=ne,
                    chunk=self.chunk, kernel=self.kernel,
                    features=self._features_now, mesh=self._mesh)
            self._state = state          # stays device-resident
            self.io["dispatches"] = n
            tick_h, fin_h = self._read_ctl(state.tick, state.finished,
                                           counts, n)
            nxt = {}
            for r, (s, n_end) in work.items():
                s._tick = s._epoch + int(tick_h[r])
                s._host_stale = True
                if (fin_h[r] != self._fin[r]).any():
                    s._new_done = True   # poll must gather this row
                    self._fresh.add(s)
                if s._tick >= n_end or bool(fin_h[r].all()):
                    continue
                # the MAX_REL_TICKS split: re-pack (re-basing the
                # epoch) and keep going toward the real target
                s._tb_dirty = True
                nxt[r] = (s, n_end)
            self._ticks, self._fin = tick_h, fin_h
            work = nxt

    @_io_accounted
    def _dispatch_async(self, work) -> None:
        """The double-buffered fast path: enqueue the fleet dispatch
        and RETURN — no control download, no host sync. The device
        (tick, finished) handles are parked as the deferred ctl; a
        chain of advances overwrites the parked pair (ticks only grow,
        so only the LAST dispatch's ctl matters) and the download
        happens once, at the next sync point (`_sync_ctl`). Untargeted
        rows ride on the possibly-STALE tick mirror as their horizon:
        a stale mirror can only under-ask, and a lane at or past its
        horizon is an exact no-op, so staleness never perturbs a row."""
        self._ensure()
        ne = self._ticks.astype(np.float32)
        for r, (s, n_end) in work.items():
            ne[r] = n_end - s._epoch     # caller checked the rel cap
        tb, ep = self._dispatch_slab()
        n = self.io["dispatches"] + 1
        # the work counters ride the chain on the device: a dispatch
        # starts from the still-parked ctl's, so ONE download covers them
        counts = self._ctl[2] if self._ctl is not None else None
        with TraceAnnotation(SPAN_DISPATCH, dispatch=n):
            state, _, counts = self._je.session_advance(
                self._state, tb, ep, n_end=ne,
                chunk=self.chunk, kernel=self.kernel,
                features=self._features_now, mesh=self._mesh,
                block=False, counts=counts)
        self._state = state              # stays device-resident
        self.io["dispatches"] = n
        self._ctl = (state.tick, state.finished, counts, n)
        for r, (s, n_end) in work.items():
            s._host_stale = True
            self._pend_rows[r] = (s, n_end)

    @_io_accounted
    def _sync_ctl(self) -> None:
        """Consume the deferred control download of the async dispatch
        chain: ONE host transfer of the tiny (tick, finished) mirrors
        and the work counters covers every dispatch enqueued since the
        last sync. MUST run
        before anything reads or writes the host ctl mirrors — poll's
        completion scan, snapshot gathers, dirty-row scatters and
        rebuilds (which overwrite mirror rows), `host_view` — so a
        stale parked ctl can never clobber fresher mirror writes."""
        if self._ctl is None:
            return
        tick_dev, fin_dev, counts, n = self._ctl
        self._ctl = None
        tick_h, fin_h = self._read_ctl(tick_dev, fin_dev, counts, n)
        pend, self._pend_rows = self._pend_rows, {}
        short = []
        for r, (s, n_end) in pend.items():
            if s._row != r or self._sessions[r] is not s:
                continue          # released (maybe recycled) row
            s._tick = s._epoch + int(tick_h[r])  # saath: lint-ok(host-pull-unaccounted): host mirror from _read_ctl, which accounts the download
            if (fin_h[r] != self._fin[r]).any():
                s._new_done = True   # poll must gather this row
                self._fresh.add(s)
            if s._tick < n_end and not bool(fin_h[r].all()):  # saath: lint-ok(host-pull-unaccounted): host mirror from _read_ctl
                short.append((r, s._tick, n_end))
        self._ticks, self._fin = tick_h, fin_h
        if short:
            raise RuntimeError(
                f"async session_advance stopped short of its horizon "
                f"on rows {short} (step budget exhausted?)")

    @_io_accounted
    def _read_ctl(self, tick_dev, fin_dev, counts, n: int) -> tuple:
        """Download dispatch `n`'s control mirrors (per-row ticks and
        completion bitmap) together with the work counters it carries,
        waiting for the device; charge the bytes to `ctl_bytes` and add
        the counters into `io`. Returns the flat (tick, finished) host
        mirrors."""
        with TraceAnnotation(SPAN_SYNC_CTL, dispatch=n):
            tick_h, fin_h, counts = jax.device_get(
                (tick_dev, fin_dev, counts))
        tick_h = np.array(tick_h).reshape(-1)
        fin_h = np.array(fin_h)
        fin_h = fin_h.reshape(-1, fin_h.shape[-1])
        self.io["ctl_bytes"] += (tick_h.nbytes + fin_h.nbytes
                                 + _tree_nbytes(counts))
        # every row of a slab (or shard) pays each loop iteration: the
        # longest loop's count is the serial device work; the per-lane
        # counters sum over rows
        self.io["event_steps"] += int(counts.event_steps.max())
        self.io["lane_steps"] += int(counts.lane_steps.sum())
        self.io["admit_trips"] += int(counts.admit_trips.sum())
        self.io["wc_trips"] += int(counts.wc_trips.sum())
        self.io["wc_fills"] += int(counts.wc_fills.sum())
        self.io["maxmin_cands"] += int(counts.maxmin_cands.sum())
        return tick_h, fin_h

    @_io_accounted
    def _plan_tick(self, sess) -> np.ndarray:
        """One wave-planning coordinator tick for ONE session row; the
        other rows are masked no-ops. Returns the row's admitted mask."""
        self._ensure()
        mask = np.zeros(self.max_sessions, bool)
        mask[sess._row] = True
        tb, ep = self._dispatch_slab()
        state, admitted = self._rows.plan_tick(
            self._state, tb, ep, kernel=self.kernel,
            features=self._features_now, row_mask=self._fold(mask))
        self._state = state
        self.io["dispatches"] += 1
        adm_all = self._unfold(np.asarray(admitted))
        self.io["ctl_bytes"] += adm_all.nbytes
        sess._host_stale = True
        self._materialize([sess])
        return adm_all[sess._row]

    @_io_accounted
    def _ensure(self) -> None:
        """Flush host-side changes to the device slab: released rows are
        re-blanked and dirty rows re-packed, both as ROW SCATTERS
        (`jax_engine.scatter_rows`) — clean rows never re-upload. A
        capacity growth (any row outgrowing the shared flow/coflow
        capacities, grown geometrically) is the one full-slab rebuild
        path; per-row state is carried through the sessions' host
        entries, so nothing is lost."""
        need_c = need_f = 0
        for s in self.sessions:
            if s._tb_dirty:
                need_c = max(need_c, len(s._live))
                need_f = max(need_f, sum(e.size.size
                                         for e in s._live.values()))
        grew = False
        while self._C_cap < need_c:
            self._C_cap *= 2
            grew = True
        while self._F_cap < need_f:
            self._F_cap *= 2
            grew = True
        if self._ep_stack is None and self._pinned is None:
            feats = [self._base_features] + \
                [self._row_feat[s._row] for s in self.sessions]
            self._features_now = tuple(
                any(f[i] for f in feats) for i in range(5))
        # pinned features stay pinned: admission already validated
        # every tenant against them, so membership churn can never
        # change the compiled structure (no recompiles)
        if bool(self._features_now[4]) != self._sampling:
            # the pilot mask is a slab LEAF: compiling sampling in (or
            # out) changes the TraceBatch structure, so the slab and
            # the packing scratch must be rebuilt from scratch
            self._sampling = bool(self._features_now[4])
            self._scratch = None
            grew = True
        if self._tb is None or grew:
            self._rebuild()
        else:
            self._scatter_dirty()
        if self._ep_stack is None:
            rows = self._row_ep
            if self._sampling or any(
                    e.dp.clairvoyant is not None for e in rows):
                # heterogeneous fleets mix clairvoyant rows (empty
                # clairvoyant subtree) with learned rows (f32 scalar);
                # stacking needs one structure, and a sampling slab
                # keeps the leaf CONCRETE even when every current
                # tenant is clairvoyant so a learned tenant joining
                # later never changes the parameter pytree
                rows = [e if e.dp.clairvoyant is not None
                        else e._replace(dp=e.dp._replace(
                            clairvoyant=jnp.float32(1.0)))
                        for e in rows]
            self._ep_stack = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *rows)
            self._ep_disp = None

    @_io_accounted
    def _scatter_dirty(self) -> None:
        from repro.traces.batch import row_of, stack_rows

        dirty = [s for s in self.sessions
                 if s._tb_dirty or s._state_dirty]
        if not dirty and not self._blank_rows:
            return
        # re-packing reads the host entries: sync the dirty rows first
        self._materialize(dirty)
        with TraceAnnotation(SPAN_STAGE):
            tb_rows, st_rows = [], []
            for r in sorted(self._blank_rows):
                self._blank_scratch()
                tb_rows.append((r, row_of(self._scratch, 0)))
                st_rows.append((r, self._blank_state_row()))
            self._blank_rows.clear()
            for s in dirty:
                if s._tb_dirty:
                    self._pack_row_np(self._scratch_tb(), 0, s)
                    tb_rows.append((s._row, row_of(self._scratch, 0)))
                st_rows.append((s._row, self._state_row(s)))
                s._state_dirty = False
            for r, row in st_rows:
                self._ticks[r] = int(row.tick)
                self._fin[r] = row.finished
            # ONE SCATTER QUEUE PER SHARD, at shard-local rows (a
            # sharded pool's scatter runs per device on its folded
            # slab). Every queue is padded to one power-of-two bucket
            # (`_bucket`) with pad rows past its shard's last row, which
            # the scatter drops: each count the slab can see ran when
            # it was built (`_warm_rows`)
            st_q = self._by_shard(st_rows)
            k = _bucket(max(len(q) for q in st_q))  # tb rows <= st rows
            st_idx, st_payload = self._pad_rows(st_q, k, st_rows[0][1],
                                                _stack)
            self.io["scatter_rows"] += self.shards * k
            self.io["pad_rows"] += self.shards * k - len(st_rows)
            self.io["upload_bytes"] += _tree_nbytes(st_payload)
            if tb_rows:
                tb_idx, tb_payload = self._pad_rows(
                    self._by_shard(tb_rows), k, tb_rows[0][1], stack_rows)
                self.io["row_uploads"] += len(tb_rows)
                self.io["upload_bytes"] += _tree_nbytes(tb_payload)
        with TraceAnnotation(SPAN_UPLOAD):
            if tb_rows:
                # one fused scatter dispatch covers both trees
                self._tb, self._state = self._rows.scatter(
                    (self._tb, self._state), (tb_idx, st_idx),
                    (tb_payload, st_payload))
            else:
                self._state = self._rows.scatter(self._state, st_idx,
                                                 st_payload)

    def _by_shard(self, items) -> list:
        """[(row, x)] -> one list per shard of (shard-local row, x)."""
        per = self.max_sessions // self.shards
        out = [[] for _ in range(self.shards)]
        for r, x in items:
            out[r // per].append((r % per, x))
        return out

    def _pad_rows(self, queues, k: int, pad, stack) -> tuple:
        """Shard-local indices and stacked payload of the per-shard
        `queues` [(local row, tree)], each padded to `k` rows with
        `pad` at local index B/shards, past its shard's last row, where
        the scatter drops it: pads leave the slab bit for bit as it
        was. In the slab's layout (`_fold`): (k,) and (k, ...)
        unsharded, (shards, k) and (shards, k, ...) sharded."""
        per = self.max_sessions // self.shards
        idx = np.full((self.shards, k), per, np.int32)
        trees = []
        for sh, q in enumerate(queues):
            idx[sh, :len(q)] = [r for r, _ in q]
            trees += [t for _, t in q] + [pad] * (k - len(q))
        return self._fold(idx.reshape(-1)), self._fold(stack(trees))

    def _warm_rows(self) -> None:
        """Run the fused and the state-only row scatter and the row
        gather once at every bucket a shard's queue can fill, on the
        slab just built, with pad rows only (which change nothing): a
        serving window then compiles and loads nothing, whatever the
        number of dirty or stale rows. (A call, not an ahead-of-time
        compile: a pmapped program compiled ahead of time is compiled
        again at its first call.)"""
        from repro.traces.batch import row_of, stack_rows

        self._blank_scratch()
        tb_pad = row_of(self._scratch, 0)
        st_pad = self._blank_state_row()
        queues = [[] for _ in range(self.shards)]
        k = 1
        while k <= _bucket(self.max_sessions // self.shards):
            tb_idx, tb_rows = self._pad_rows(queues, k, tb_pad, stack_rows)
            st_idx, st_rows = self._pad_rows(queues, k, st_pad, _stack)
            self.io["upload_bytes"] += (_tree_nbytes(tb_rows)
                                        + 2 * _tree_nbytes(st_rows))
            self._tb, self._state = self._rows.scatter(
                (self._tb, self._state), (tb_idx, st_idx),
                (tb_rows, st_rows))
            self._state = self._rows.scatter(self._state, st_idx, st_rows)
            self._rows.gather(self._state, np.zeros_like(st_idx))
            k *= 2

    def _scratch_tb(self):
        from repro.traces.batch import empty_batch

        if self._scratch is None:
            self._scratch = empty_batch(
                1, flow_capacity=self._F_cap,
                coflow_capacity=self._C_cap,
                port_capacity=self.num_ports,
                leaf_links=self._Lf,
                sampling=self._sampling)
        return self._scratch

    def _blank_scratch(self):
        from repro.traces.batch import blank_row

        blank_row(self._scratch_tb(), 0)

    @_io_accounted
    def _rebuild(self) -> None:
        """Full-slab rebuild (first build, or a capacity growth): pack
        every row host-side and upload the whole slab once — the ONLY
        path that moves full mirrors to the device."""
        from repro.traces.batch import empty_batch

        self._materialize()
        self._scratch = None
        with TraceAnnotation(SPAN_STAGE):
            tb = empty_batch(self.max_sessions,
                             flow_capacity=self._F_cap,
                             coflow_capacity=self._C_cap,
                             port_capacity=self.num_ports,
                             leaf_links=self._Lf,
                             sampling=self._sampling)
            rows = [self._blank_state_row()
                    for _ in range(self.max_sessions)]
            self._blank_rows.clear()
            for s in self.sessions:
                s._tb_dirty = True
                self._pack_row_np(tb, s._row, s)
                rows[s._row] = self._state_row(s)
                s._state_dirty = False
            state = _stack(rows)
        self.io["full_uploads"] += 1
        self.io["upload_bytes"] += _tree_nbytes(tb) + _tree_nbytes(state)
        # the upload pins the row sharding: each shard receives exactly
        # its own rows (sharding=None -> default single-device slab),
        # in dispatch layout (the fold is a free host-side numpy reshape)
        with TraceAnnotation(SPAN_UPLOAD):
            self._tb = jax.device_put(self._fold(tb), self._sharding)
            self._state = jax.device_put(self._fold(state),
                                         self._sharding)
        self._ticks = state.tick.copy()
        self._fin = state.finished.copy()
        self._warm_rows()

    def _fold(self, tree):
        """Reshape every leaf of a HOST tree (B, ...) -> (shards,
        B/shards, ...): the pmap dispatch layout of a sharded pool
        (identity when unsharded)."""
        if self.shards <= 1:
            return tree
        S = self.shards
        return jax.tree_util.tree_map(
            lambda x: x.reshape(S, x.shape[0] // S, *x.shape[1:]),
            tree)

    def _unfold(self, tree):
        """Inverse of `_fold` on a HOST tree."""
        if self.shards <= 1:
            return tree
        return jax.tree_util.tree_map(
            lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
            tree)

    def _dispatch_slab(self):
        """The (tb, ep) pair in dispatch layout: the stored slab, and
        the stacked parameters folded and placed once per restack (they
        change only on membership churn, never per advance, so the
        async dispatch hot loop moves no parameters)."""
        if self.shards <= 1:
            return self._tb, self._ep_stack
        if self._ep_disp is None:
            self._ep_disp = jax.device_put(self._fold(self._ep_stack),
                                           self._sharding)
        return self._tb, self._ep_disp

    def _pack_row_np(self, tb, r: int, s) -> None:
        """Pack one session's live coflows into row `r` of a NUMPY
        TraceBatch (the 1-row scratch for scatters, the full slab for
        rebuilds), re-basing the row's grid epoch when due."""
        from repro.traces.batch import pack_row

        if s._tick - s._epoch >= REBASE_TICKS:
            # re-base the row's grid epoch: all slab times below are
            # stored relative to it, restoring δ resolution in f32
            s._epoch = s._tick
        table = s._rebuild_table()
        pack_row(tb, r, table,
                 arrival_rank=[e.rank for e in s._slots],
                 topology=self.topology if self._Lf else None,
                 pilot_frac=s.params.pilot_frac)
        s._flow_lo = table.flow_lo.copy()
        s._flow_hi = table.flow_hi.copy()
        s._tb_dirty = False

    def _blank_state_row(self):
        from repro.core.jax_coordinator import CoordState
        from repro.fabric.jax_engine import EngineState

        C, F = self._C_cap, self._F_cap
        return EngineState(
            coord=CoordState(np.full((C,), -1, np.int32),
                             np.full((C,), np.inf, np.float32),
                             np.zeros((C,), bool)),
            sent=np.zeros((F,), np.float32),
            done=np.ones((F,), bool),
            fct=np.zeros((F,), np.float32),
            finished=np.ones((C,), bool),
            cct=np.full((C,), np.nan, np.float32),
            t0=np.float32(0.0),
            tick=np.int32(0),
            rate=np.zeros((F,), np.float32),
            pend_sent=np.zeros((F,), np.float32),
            pend_tick=np.float32(0.0),
            pend_next=np.float32(0.0))

    def _state_row(self, s):
        """One row of engine state rebuilt from the session's host
        entries (the carry that survives re-packs), as unbatched numpy
        arrays ready to scatter. Pads (and retired slots) stay at the
        blank-row identity: done/finished, zero rates."""
        row = self._blank_state_row()
        epoch_t = s._epoch * s.params.delta
        for i, e in enumerate(s._slots):
            lo, hi = s._flow_lo[i], s._flow_hi[i]
            row.sent[lo:hi] = e.sent
            row.done[lo:hi] = e.done
            row.fct[lo:hi] = np.where(
                e.done, np.nan_to_num(e.fct) - epoch_t, 0.0)
            row.finished[i] = e.finished
            row.cct[i] = e.cct
            row.coord.queue[i] = e.queue
            row.coord.deadline[i] = e.deadline - epoch_t \
                if np.isfinite(e.deadline) else np.inf
            row.coord.running[i] = e.running
            row.rate[lo:hi] = e.rate
            row.pend_sent[lo:hi] = e.pend_sent
        row = row._replace(tick=np.int32(s._tick - s._epoch))
        if s._pend is not None:
            row = row._replace(
                pend_tick=np.float32(s._pend[0] - s._epoch),
                pend_next=np.float32(s._pend[1] - s._epoch))
        return row

    @_io_accounted
    def _materialize(self, sessions=None,
                     completions_only: bool = False) -> None:
        """Gather STALE rows of the device state back into their
        sessions' host entries — one `gather_rows` dispatch for the
        whole stale set (absolute f64 times reconstructed from the row
        epochs). Clean host mirrors cost nothing; this is the lazy
        half of the device-resident contract. `sessions` restricts the
        sync to the rows a caller actually inspects (a snapshot of one
        tenant never downloads its neighbors); `completions_only`
        (the poll fast path) syncs only rows whose dispatch-status
        mirror shows NEW completions — a row that merely progressed
        stays stale (and free) until a re-pack or snapshot needs it.
        A sync point of the async dispatch contract: the deferred ctl
        is consumed before the stale/new-done flags are read."""
        if self._state is None:
            return
        self._sync_ctl()
        if completions_only and not self._fresh:
            return                    # clean fleet: O(1), no roster walk
        stale = [s for s in (self.sessions if sessions is None
                             else sessions)
                 if s._host_stale
                 and (s._new_done or not completions_only)]
        if not stale:
            return
        with TraceAnnotation(SPAN_GATHER):
            # one queue per shard at shard-local rows, padded to one
            # bucket like the scatters (`_bucket`); the pads gather the
            # shard's first row and are not read
            queues = self._by_shard((s._row, s) for s in stale)
            k = _bucket(max(len(q) for q in queues))
            idx = np.zeros((self.shards, k), np.int32)
            at = {}
            for sh, q in enumerate(queues):
                for j, (r, s) in enumerate(q):
                    idx[sh, j] = r
                    at[s] = sh * k + j
            rows = self._rows.gather(self._state,
                                     self._fold(idx.reshape(-1)))
            host = self._unfold(jax.tree_util.tree_map(np.asarray, rows))
            self.io["row_downloads"] += len(stale)
            self.io["download_bytes"] += _tree_nbytes(host)
            for s in stale:
                self._sync_row(s, host, at[s])
                s._host_stale = False
                s._new_done = False
                self._fresh.discard(s)

    def _sync_row(self, s, st, j: int) -> None:
        """Mirror row `j` of the gathered host state into session `s`'s
        entries (absolute f64 times reconstructed from the row
        epoch)."""
        epoch_t = s._epoch * s.params.delta
        sent = np.asarray(st.sent[j], np.float64)
        done = np.asarray(st.done[j])
        fct = np.asarray(st.fct[j], np.float64)
        finished = np.asarray(st.finished[j])
        cct = np.asarray(st.cct[j], np.float64)
        queue = np.asarray(st.coord.queue[j])
        deadline = np.asarray(st.coord.deadline[j], np.float64)
        running = np.asarray(st.coord.running[j])
        rate = np.asarray(st.rate[j], np.float64)
        pend_sent = np.asarray(st.pend_sent[j], np.float64)
        for i, e in enumerate(s._slots):
            lo, hi = s._flow_lo[i], s._flow_hi[i]
            e.sent = sent[lo:hi].copy()
            e.done = done[lo:hi].copy()
            e.fct = np.where(e.done, fct[lo:hi] + epoch_t, np.nan)
            e.rate = rate[lo:hi].copy()
            e.pend_sent = pend_sent[lo:hi].copy()
            e.finished = bool(finished[i])
            e.cct = float(cct[i])
            e.queue = int(queue[i])
            e.deadline = float(deadline[i] + epoch_t)
            e.running = bool(running[i])
        tick_rel = int(st.tick[j])
        s._tick = s._epoch + tick_rel
        self._ticks[s._row] = tick_rel        # keep the ctl mirror true
        if not s._host_done and \
                any(e.finished for e in s._live.values()):
            s._host_done = True   # gathered completions await a poll;
            # keep the row visible to the harvest bitmap even though
            # `_new_done` is consumed by this gather
        pn = float(st.pend_next[j])
        s._pend = (s._epoch + int(st.pend_tick[j]), s._epoch + int(pn)) \
            if pn > tick_rel else None

    # ---- debug/oracle view ----------------------------------------------

    @_io_accounted
    def host_view(self) -> tuple:
        """Materialize NUMPY copies of the device slab as
        (TraceBatch, EngineState) — the lazily-built debug/oracle view
        (the device arrays stay authoritative; mutating the copies has
        no effect). Returns (None, None) before the first dispatch."""
        if self._tb is None:
            return None, None
        self._sync_ctl()
        # a sharded slab comes back folded: unfold it on the host
        tb_h, st_h = self._unfold(jax.tree_util.tree_map(
            np.asarray, (self._tb, self._state)))
        # a full-slab download: account it like any other host pull so
        # `pool.io` stays the single source of truth for transfers
        self.io["download_bytes"] += _tree_nbytes(tb_h) + _tree_nbytes(st_h)
        return tb_h, st_h


__all__ = ["SessionPool", "PoolFullError", "REBASE_TICKS"]
