"""Asynchronous SLO-aware serving session (DESIGN.md SS7 phase F).

The batch-synchronous ``AQPService.answer(List[Query])`` drains the lane
pool completely between calls: a query arriving mid-flight waits for the
whole previous batch.  :class:`AQPSession` replaces that contract with an
open-loop one -- the shape a service under continuous traffic needs:

* :meth:`submit` (``Request -> SessionTicket``) enqueues a request into the
  live arrival queue and returns immediately; the request carries its SLO
  envelope (``deadline_s``, ``priority``) alongside the MISS error clause.
* :meth:`pump` runs ONE non-blocking scheduler round: admit arrivals
  (routing each through the :class:`~repro.serve.planner.Planner`), tick
  the busy pool tiers once, harvest retirees.  Crucially the lane pool
  accepts admissions while in flight -- a request submitted between pumps
  splices into a freed lane without waiting for the pool to drain.
* :meth:`poll` (non-blocking) pops a finished response, or returns None
  while the request is still queued / in flight.
* :meth:`drain` pumps until idle -- the compatibility shape:
  ``AQPService.answer`` is now a thin submit-all-then-drain wrapper.

Routing is the planner's explicit :class:`Route` enum -- POOL (continuous
lanes, real submit->harvest latency), BATCHED (phase-C closed-loop func
groups, amortized dispatch/k latency), LOOP (one dispatch per query),
HOST (everything the fused program can't run).  The planner also re-tunes
the pool continuously from a sliding window of the live stream: sync
cadence (``ticks_per_sync``) may change between any two dispatches, and
lane-count rebuilds are requested by the planner and honored here at idle
points only (no resident state to migrate).

Sample reuse (SS3.2) carries over from the service: one resident
SampleStore per dataset shared by the host engine and every request, one
``sample_key`` per epoch pinning the fused slot->row binding.  The epoch
policy is now completion-counted, and a reshuffle firing while pool
tickets are in flight DEFERS the pool's rebind to an idle point
(:meth:`LanePool.request_sample_key`) -- resident prefixes are defined by
the old binding, so rotating under them would break the nesting invariant.

Accounting matches the service it replaces (``fused_dispatches``,
``rows_touched``), with one deliberate fix: fused rows are counted at
HARVEST time, so a response nobody ever collects (a residue ticket of an
abandoned caller) still lands in ``rows_touched``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..aqp.engine import AQPEngine
from ..aqp.query import Query, Request
from ..core import estimators
from ..core.fused import fused_l2miss_batch
from ..core.sampling import GroupedData, SampleStore
from ..kernels import resolve_use_kernel
from .lane_pool import GroupPoolResponse, LanePool
from .planner import Planner, Route, fusable, grouped_fusable
from .tracing import PhaseRecorder
from .warm_cache import CachedAnswer, WarmCache, WarmEntry

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SessionTicket:
    """Handle returned by :meth:`AQPSession.submit`; poll with it."""
    rid: int                # the request's stable id
    submitted_s: float      # perf_counter at submission (the SLO clock 0)


@dataclasses.dataclass
class SessionResponse:
    """One finished request.

    ``latency_s`` is the real submit -> completion time on every route --
    the clock the SLO is judged against.  ``wall_time_s`` keeps the
    route-specific compute-latency semantics of the synchronous service
    (real latency on POOL/LOOP/HOST; amortized dispatch/k on BATCHED), so
    the ``answer()`` compat wrapper reports exactly what it used to.
    """
    rid: int
    theta: np.ndarray
    error: float
    success: bool
    n: np.ndarray
    wall_time_s: float
    latency_s: float
    queue_wait_s: float
    route: Route
    rows_sampled: int
    deadline_s: Optional[float] = None
    slo_met: Optional[bool] = None      # None when no deadline was set
    # Phase J: the delivered contract under overload.  A ``degraded``
    # answer ran at ``delivered_epsilon > epsilon`` (relaxed at admission
    # to fit the deadline); a ``shed`` answer is an n_min pilot whose
    # delivered epsilon is its measured error bar.  Either way the answer
    # satisfies ``error <= delivered_epsilon`` at the request's delta.
    epsilon: Optional[float] = None            # requested bound
    delivered_epsilon: Optional[float] = None  # bound actually satisfied
    delivered_B: Optional[int] = None          # replicate count actually run
    degraded: bool = False
    shed: bool = False
    # GROUP BY requests (phase I): ``theta``/``n`` hold one row per group,
    # ``error``/``success`` the scalar summary (max over groups / the
    # conjunction), and the per-group quantiles and verdicts land here.
    group_by: bool = False
    group_error: Optional[np.ndarray] = None     # (G,)
    group_success: Optional[np.ndarray] = None   # (G,)


def _request_eps(q: Query) -> float:
    """The bound value a cached answer is keyed on: the absolute epsilon,
    the relative epsilon, or 1.0 for the parameterless order metric (the
    bound-kind lives in the signature shape, so the three never collide)."""
    if q.metric == "order":
        return 1.0
    if q.epsilon is not None:
        return float(q.epsilon)
    return float(q.epsilon_rel)


@dataclasses.dataclass
class _InFlight:
    ticket: SessionTicket
    request: Request
    key: Optional[np.ndarray]           # explicit bootstrap key, if any
    route: Optional[Route] = None       # set at admission
    # Phase H warm-cache state, resolved at submit():
    sig: Optional[tuple] = None         # cache signature (None: uncacheable)
    warm_n0: Optional[np.ndarray] = None    # (m,) predicted n* (warm hit)
    warm_beta: Optional[np.ndarray] = None  # (m+1,) cached coefficients


class AQPSession:
    """Serve Listing-1 requests asynchronously against one resident
    GroupedData."""

    def __init__(self, data: GroupedData, *, B: int = 300,
                 n_min: int = 1000, n_max: int = 2000, max_iters: int = 24,
                 n_cap: int = 1 << 16, seed: int = 0,
                 reshuffle_every: int = 256,
                 use_kernel: "bool | str" = "auto",
                 planner: Optional[Planner] = None,
                 pool_tiers: "int | str" = "auto",
                 data_shards: int = 1, mesh=None,
                 warm_cache: "bool | WarmCache" = False,
                 degrade: bool = False, wfq: bool = False,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 migrate: bool = False, max_degrade: float = 8.0):
        self.data = data
        self.store = SampleStore(data, seed=seed)
        self.engine = AQPEngine(data, B=B, n_min=n_min, n_max=n_max,
                                seed=seed, store=self.store,
                                use_kernel=use_kernel)
        self.B, self.n_min, self.n_max = B, n_min, n_max
        self.max_iters, self.n_cap = max_iters, n_cap
        self.seed = seed
        self.use_kernel = resolve_use_kernel(use_kernel)
        # Phase G: a data mesh multiplies pool capacity; the planner's lane
        # ceiling scales with it, the rest of the host scheduler is unaware.
        self.data_shards = max(int(data_shards), 1)
        self.mesh = mesh
        # Phase J: overload-native scheduling, all OPT-IN (the phase-E/F
        # session is the exact special case).  ``degrade`` arms
        # deadline-driven epsilon relaxation + load shedding in the pool
        # (and biases the auto planner toward POOL for deadline-carrying
        # requests -- only the pool can degrade); ``wfq`` arms per-tenant
        # weighted fair queueing; ``migrate`` arms cross-tier lane
        # migration.
        self.degrade = bool(degrade)
        self.wfq = bool(wfq)
        self.tenant_weights = tenant_weights
        self.migrate = bool(migrate)
        self.max_degrade = float(max_degrade)
        self.planner = (planner if planner is not None
                        else Planner(data_shards=self.data_shards,
                                     slo_native=self.degrade))
        self.pool_tiers = pool_tiers
        self.key = jax.random.PRNGKey(seed)
        self._offsets = jnp.asarray(data.offsets)
        self._m = data.num_groups
        # Reuse/decorrelation policy: one sample epoch serves up to
        # ``reshuffle_every`` COMPLETED requests, then prefixes are redrawn
        # (the pool's rebind deferred to its next idle point).
        self.reshuffle_every = int(reshuffle_every)
        self._queries_in_epoch = 0
        self._epoch_counter = 0
        self._sample_root = jax.random.PRNGKey(seed ^ 0x5A17)
        self._sample_key = jax.random.fold_in(self._sample_root, 0)
        # Live scheduling state.
        self._arrivals: Deque[int] = deque()            # rids awaiting route
        self._inflight: Dict[int, _InFlight] = {}       # rid -> entry
        self._results: Dict[int, SessionResponse] = {}  # rid -> response
        self._pool: Optional[LanePool] = None
        self._pool_rids: Dict[int, int] = {}            # pool qid -> rid
        # Phase H: learned warm-start + answer cache.  OPT-IN: repeat
        # detection changes how a bit-identical resubmission is served
        # (replayed, zero dispatches), so callers that rely on every
        # submission running -- parity tests, scheduling benchmarks --
        # keep the default off.
        if isinstance(warm_cache, WarmCache):
            self.cache: Optional[WarmCache] = warm_cache
        else:
            self.cache = WarmCache() if warm_cache else None
        self.warm_verify_failures = 0   # warm lanes that needed > 1 iter
        self.cache_served = 0           # exact-answer replays (0 dispatches)
        # Accounting (the service contract).
        self._fused_rows = 0
        self.fused_dispatches = 0
        self.submitted = 0
        self.completed = 0
        self.pool_rebuilds = 0
        # Phase spans and counters; shared with every pool built here, so
        # they survive pool rebuilds.
        self.recorder = PhaseRecorder()

    # -- public surface -----------------------------------------------------
    @property
    def rows_touched(self) -> int:
        """Cumulative rows sampled across ALL paths: host-engine store
        gathers plus every fused lane's filled watermark -- counted at
        harvest, so uncollected residue responses are never lost."""
        return self.store.rows_touched + self._fused_rows

    @property
    def pool(self) -> Optional[LanePool]:
        """The live lane pool (None until the first pooled request)."""
        return self._pool

    @property
    def in_flight(self) -> int:
        """Requests submitted but not yet finished (queued or running)."""
        return len(self._inflight)

    def submit(self, request: Request,
               key: Optional[Array] = None) -> SessionTicket:
        """Enqueue one request into the live arrival queue (non-blocking;
        the next :meth:`pump` admits it).  ``key`` optionally pins the
        bootstrap key -- reproducibility hooks for tests and replay."""
        if not isinstance(request, Request):
            raise TypeError(
                f"submit() takes a Request (got {type(request).__name__}); "
                f"wrap the Query: Request(query=...)")
        if request.rid in self._inflight or request.rid in self._results:
            raise ValueError(f"request id {request.rid} already submitted")
        with self.recorder.phase("submit"):
            ticket = SessionTicket(rid=request.rid,
                                   submitted_s=time.perf_counter())
            entry = _InFlight(ticket=ticket, request=request,
                              key=None if key is None else np.asarray(key))
            self._inflight[request.rid] = entry
            self.submitted += 1
            # Phase H: resolve the warm cache at submit time.  An explicitly
            # pinned bootstrap key is a replay/repro contract the cache must
            # not alias, so pinned requests bypass it entirely.
            if self.cache is not None and entry.key is None \
                    and self._cache_resolve(entry):
                return ticket   # exact replay: answered, zero dispatches
            self._arrivals.append(request.rid)
            return ticket

    def _cache_resolve(self, entry: _InFlight) -> bool:
        """Submit-time cache lookup.  True = the request was answered
        outright (bit-identical repeat replayed from the cache: it never
        enters the arrival queue).  Otherwise annotates the entry with
        warm-start state (predicted ``n0`` + cached coefficients) for the
        WARM route and returns False."""
        q = entry.request.query
        entry.sig = self.cache.signature(
            q, num_groups=self._m if q.group_by else None)
        if entry.sig is None:
            return False        # opaque callable predicate: uncacheable
        kind, ce = self.cache.lookup(entry.sig, epsilon=_request_eps(q))
        if kind == "exact":
            a = ce.answer
            self.cache_served += 1
            # No rows were sampled, so the replay must not advance the
            # reuse epoch (it would spuriously trigger reshuffles).
            self._complete(
                entry, theta=a.theta.copy(), error=a.error,
                success=a.success, n=a.n.copy(), wall_time_s=0.0,
                queue_wait_s=0.0, route=Route.WARM, rows_sampled=0,
                count_epoch=False,
                group_error=None if a.group_error is None
                else a.group_error.copy(),
                group_success=None if a.group_success is None
                else a.group_success.copy())
            return True
        if kind == "warm" and (fusable(entry.request)
                               or grouped_fusable(entry.request)):
            entry.warm_n0 = self.cache.predict_n0(
                ce, epsilon=float(q.epsilon), n_min=self.n_min)
            entry.warm_beta = np.asarray(ce.beta, np.float32).copy()
        return False

    def _cache_insert(self, entry: _InFlight, *, beta, n, theta, error,
                      success: bool, failed: bool, iterations: int,
                      group_error=None, group_success=None) -> None:
        """Teach the cache what one completed run learned.  Skipped for
        pinned-key runs (``entry.sig`` is None then), unsuccessful or
        Algorithm-2-failed runs, and entries whose signature predates the
        current epoch -- a rotation fired while this run was in flight, so
        its rows were drawn under the dead slot->row binding.  Grouped runs
        pass their per-group quantiles/verdicts so an exact replay restores
        the full per-group response."""
        if (self.cache is None or entry.sig is None or failed
                or not success or entry.sig[0][0] != self.cache.epoch):
            return
        n = np.asarray(n)
        b = (np.zeros(n.shape[0] + 1, np.float32) if beta is None
             else np.asarray(beta, np.float32).copy())
        eps = _request_eps(entry.request.query)
        self.cache.insert(entry.sig, WarmEntry(
            beta=b, n_star=n.copy(), iterations=int(iterations), epsilon=eps,
            answer=CachedAnswer(
                theta=np.asarray(theta).copy(), error=float(error),
                success=True, n=n.copy(), epsilon=eps,
                group_error=None if group_error is None
                else np.asarray(group_error).copy(),
                group_success=None if group_success is None
                else np.asarray(group_success).copy())))

    def poll(self, ticket: Union[SessionTicket, int]
             ) -> Optional[SessionResponse]:
        """Pop the finished response for ``ticket``, or None while it is
        still in flight.  Unknown (or already-collected) tickets raise."""
        rid = ticket.rid if isinstance(ticket, SessionTicket) else int(ticket)
        if rid in self._results:
            return self._results.pop(rid)
        if rid in self._inflight:
            return None
        raise KeyError(f"unknown or already-collected ticket: rid={rid}")

    def pump(self) -> int:
        """One non-blocking scheduler round: re-tune, admit arrivals, tick
        busy tiers once, harvest retirees.  Returns requests in flight."""
        rec = self.recorder
        with rec.phase("pump"):
            with rec.phase("retune"):
                self._retune()
            with rec.phase("admit"):
                self._admit()
            pool = self._pool
            if pool is not None and (pool.busy_lanes or pool.busy_blocks
                                     or pool.queue_depth):
                d0 = pool.dispatches
                pool.tick()
                self.fused_dispatches += pool.dispatches - d0
            # Unconditional: a shed request (phase J) is pilot-answered
            # inside submit()/tick() without ever occupying a lane, so the
            # pool can hold results while reporting zero busy lanes and an
            # empty queue.
            with rec.phase("collect"):
                self._harvest_pool()
            return self.in_flight

    def drain(self, max_pumps: int = 100_000) -> List[SessionResponse]:
        """Pump until nothing is in flight; pop and return every finished
        response not yet polled, in rid order.  Popping keeps an unbounded
        stream at bounded memory -- ``drain`` and ``poll`` both consume."""
        guard = 0
        while self._inflight and guard < max_pumps:
            self.pump()
            guard += 1
        return [self._results.pop(rid) for rid in sorted(self._results)]

    def refresh(self, data: Optional[GroupedData] = None) -> None:
        """Invalidate resident samples after a data update (idle only)."""
        if self._inflight:
            raise RuntimeError(
                "cannot refresh() with requests in flight; drain() first")
        if data is not None:
            self.data = data
            self.engine.data = data
            self._offsets = jnp.asarray(data.offsets)
            self._m = data.num_groups
        self.store.refresh(self.data)
        self._pool = None               # resident prefixes follow the data
        self._rotate_epoch()

    def stats(self) -> Dict[str, float]:
        out = {
            "submitted": self.submitted,
            "completed": self.completed,
            "in_flight": self.in_flight,
            "fused_dispatches": self.fused_dispatches,
            "rows_touched": self.rows_touched,
            "pool_rebuilds": self.pool_rebuilds,
            "sample_epoch": self._epoch_counter,
            # Where a pump's host time goes: self time and calls per phase
            # span (tracing.PhaseRecorder), and the blocking fetches.
            "phases": self.recorder.stats(),
            "syncs": self.recorder.syncs,
        }
        if self.cache is not None:
            out["cache_hits"] = self.cache.hits
            out["cache_misses"] = self.cache.misses
            out["cache_evictions"] = self.cache.evictions
            out["cache_served"] = self.cache_served
            out["warm_verify_failures"] = self.warm_verify_failures
            out["warm_cache"] = self.cache.stats()
        if self._pool is not None:
            out["pool"] = self._pool.stats()
        return out

    # -- epoch policy -------------------------------------------------------
    def _rotate_epoch(self) -> None:
        self._epoch_counter += 1
        self._queries_in_epoch = 0
        self._sample_key = jax.random.fold_in(
            self._sample_root, self._epoch_counter)
        if self.cache is not None:
            # Cached answers/coefficients were learned under the old
            # slot->row binding -- drop them (and bump the signature epoch
            # so in-flight runs of the old epoch skip their inserts).
            self.cache.rotate_epoch()
        if self._pool is not None:
            # Deferred: applied immediately if the pool is idle, else at
            # its next idle point -- never under a resident prefix.
            self._pool.request_sample_key(self._sample_key)

    def _account_completion(self) -> None:
        self.completed += 1
        self.planner.observe_completion()
        self._queries_in_epoch += 1
        if self._queries_in_epoch >= self.reshuffle_every:
            self.store.reshuffle()
            self._rotate_epoch()

    def _complete(self, entry: _InFlight, *, theta, error, success, n,
                  wall_time_s: float, queue_wait_s: float, route: Route,
                  rows_sampled: int, now: Optional[float] = None,
                  count_epoch: bool = True, group_error=None,
                  group_success=None, delivered_epsilon=None,
                  delivered_B=None, degraded: bool = False,
                  shed: bool = False) -> None:
        now = time.perf_counter() if now is None else now
        latency = now - entry.ticket.submitted_s
        ddl = entry.request.deadline_s
        self._results[entry.request.rid] = SessionResponse(
            rid=entry.request.rid, theta=theta, error=error, success=success,
            n=n, wall_time_s=wall_time_s, latency_s=latency,
            queue_wait_s=queue_wait_s, route=route,
            rows_sampled=rows_sampled, deadline_s=ddl,
            slo_met=None if ddl is None else latency <= ddl,
            group_by=bool(entry.request.query.group_by),
            group_error=group_error, group_success=group_success,
            epsilon=entry.request.query.epsilon,
            delivered_epsilon=delivered_epsilon, delivered_B=delivered_B,
            degraded=degraded, shed=shed)
        del self._inflight[entry.request.rid]
        if count_epoch:
            self._account_completion()
        else:
            self.completed += 1     # cache replay: outside the epoch policy

    # -- pool management ----------------------------------------------------
    def _build_pool(self, lanes: int, ticks_per_sync: int) -> LanePool:
        pool = LanePool(
            self.data, lanes=lanes, B=self.B, n_min=self.n_min,
            n_max=self.n_max, max_iters=self.max_iters, n_cap=self.n_cap,
            use_kernel=self.use_kernel, seed=self.seed,
            sample_key=self._sample_key, ticks_per_sync=ticks_per_sync,
            tiers=self.pool_tiers, data_shards=self.data_shards,
            mesh=self.mesh, degrade=self.degrade, wfq=self.wfq,
            tenant_weights=self.tenant_weights, migrate=self.migrate,
            max_degrade=self.max_degrade, recorder=self.recorder)
        self.planner.built_pool(lanes)
        return pool

    def _ensure_pool(self) -> LanePool:
        if self._pool is None:
            plan = self.planner.pool_plan()
            self._pool = self._build_pool(plan.lanes, plan.ticks_per_sync)
            # Pre-warm every admission-wave split bucket (see _KEY_BUCKETS):
            # one-time ~log2 compiles here instead of latency spikes on the
            # first burst of each novel size mid-serving.  Only the split
            # SHAPES matter; self.key is untouched (no split consumed).
            for b in self._KEY_BUCKETS:
                jax.random.split(self.key, b)
        return self._pool

    def _retune(self) -> None:
        """Apply the planner's sliding-window policy to the live pool:
        ``ticks_per_sync`` between any two dispatches (shapes only future
        dispatches -- trajectory-invariant), lane-count rebuilds at idle
        points only."""
        pool = self._pool
        if pool is None:
            return
        plan = self.planner.pool_plan(current_lanes=pool.lanes)
        if plan.ticks_per_sync != pool.ticks_per_sync:
            pool.ticks_per_sync = plan.ticks_per_sync
            self.planner.retunes += 1
        if (plan.rebuild and not pool.busy_lanes and not pool.busy_blocks
                and not pool.queue_depth and not pool.results):
            # Idle: no resident state, no uncollected retirees.  The new
            # pool starts at the CURRENT epoch key, so a rotation the old
            # pool had parked is applied by construction.
            self._pool = self._build_pool(plan.lanes, plan.ticks_per_sync)
            self.pool_rebuilds += 1

    # -- admission ----------------------------------------------------------
    def _admit(self) -> None:
        """Route every queued arrival; synchronous routes (BATCHED / LOOP /
        HOST) complete inside this call, POOL submissions ride subsequent
        pumps."""
        if not self._arrivals:
            return
        wave = [self._inflight[rid] for rid in self._arrivals]
        self._arrivals.clear()
        pool = self._pool
        pool_busy = pool is not None and bool(
            pool.busy_lanes or pool.busy_blocks or pool.queue_depth)
        # Warm-cache hits are short-lived lanes by construction; feeding
        # them into the planner's sliding windows would let a burst of
        # repeats inflate the lane-count drift signal and trigger rebuilds.
        n_fus = 0
        for e in wave:
            if fusable(e.request) and e.warm_n0 is None:
                n_fus += 1
                self.planner.observe_request(e.request)
        self.planner.observe_backlog(
            n_fus + ((pool.busy_lanes + pool.queue_depth) if pool else 0))
        groups: Dict[Route, List[_InFlight]] = {}
        for e in wave:
            e.route = self.planner.route(
                e.request, pending_fusable=n_fus, pool_busy=pool_busy,
                warm=e.warm_n0 is not None)
            groups.setdefault(e.route, []).append(e)
        try:
            # WARM rides the pool machinery (a warm-started lane admitted
            # into the narrowest free tier by the pool's placement rule).
            pooled_entries = groups.get(Route.POOL, []) + \
                groups.get(Route.WARM, [])
            if pooled_entries:
                self._admit_pool(pooled_entries)
            if groups.keys() & {Route.BATCHED, Route.LOOP, Route.HOST}:
                # The synchronous routes stall every lane until they return.
                with self.recorder.phase("inline_route"):
                    if Route.BATCHED in groups:
                        self._run_batched(groups[Route.BATCHED])
                    if Route.LOOP in groups:
                        self._run_loop(groups[Route.LOOP])
                    for e in groups.get(Route.HOST, ()):
                        self._run_host(e)
        except BaseException:
            # A synchronous route died mid-wave (engine error, interrupt).
            # Entries not yet completed and not handed to the pool would
            # otherwise be stranded in _inflight with no way back to the
            # scheduler -- re-queue them so the next pump() retries (the
            # failing request included; a poisoned query keeps raising to
            # its caller rather than silently vanishing).
            pooled = set(self._pool_rids.values())
            stranded = [e.request.rid for e in wave
                        if e.request.rid in self._inflight
                        and e.request.rid not in pooled]
            self._arrivals.extendleft(reversed(stranded))
            raise

    # Admission-wave key splits are bucketed to powers of two: jax compiles
    # one split program PER SPLIT COUNT, and open-loop arrival bursts make
    # the wave size effectively random -- unbucketed, a novel burst size
    # costs a ~100-300ms compile in the middle of the serving hot path
    # (a deadline-killer under phase-J load).  Buckets bound the program
    # count to log2(max wave) and are pre-warmed at pool build.
    _KEY_BUCKETS = (2, 4, 8, 16, 32, 64)

    def _lane_keys(self, entries: List[_InFlight]) -> List[Array]:
        """Per-entry bootstrap keys: ONE split covers the group (one host
        round-trip), with explicitly pinned keys taking their slot.  The
        split count rounds up to a pre-warmed power-of-two bucket; surplus
        keys are discarded."""
        n = len(entries)
        m = next((b for b in self._KEY_BUCKETS if b > n), n + 1)
        self.key, *ks = jax.random.split(self.key, m)
        return [k if e.key is None else jnp.asarray(e.key)
                for e, k in zip(entries, ks[:n])]

    def _admit_pool(self, entries: List[_InFlight]) -> None:
        pool = self._ensure_pool()
        for e, key in zip(entries, self._lane_keys(entries)):
            req = e.request
            if req.query.group_by:
                # Phase I: a grouped request admits atomically as a lane
                # BLOCK -- no ticket queue, no priority/deadline reorder
                # (it starts ticking immediately).
                qid = pool.submit_group(req.query, key=key,
                                        warm_n0=e.warm_n0,
                                        warm_beta=e.warm_beta)
            else:
                deadline_at = (None if req.deadline_s is None
                               else e.ticket.submitted_s + req.deadline_s)
                qid = pool.submit(req.query, key=key, priority=req.priority,
                                  deadline_at=deadline_at,
                                  warm_n0=e.warm_n0, warm_beta=e.warm_beta,
                                  tenant=req.tenant)
            self._pool_rids[qid] = req.rid

    def _harvest_pool(self) -> None:
        pool = self._pool
        if pool is None or not pool.results:
            return
        now = time.perf_counter()
        for qid in sorted(pool.results):
            r = pool.results.pop(qid)
            # Harvest-time accounting: the rows were gathered whether or
            # not anyone ever polls this response.
            self._fused_rows += r.rows_sampled
            rid = self._pool_rids.pop(qid, None)
            if rid is None:
                continue        # foreign ticket (pool shared out-of-band)
            entry = self._inflight[rid]
            warm = entry.warm_n0 is not None
            grouped = isinstance(r, GroupPoolResponse)
            degraded = bool(getattr(r, "degraded", False))
            shed = bool(getattr(r, "shed", False))
            its = int(np.max(r.iterations)) if grouped else int(r.iterations)
            if warm and not shed and its > 1:
                # The cached prediction did not verify in one tick; the
                # lane fell through to the normal extend loop (still
                # correct, just not O(1) -- the counter is the signal).
                self.warm_verify_failures += 1
            err = float(np.max(r.error)) if grouped else float(r.error)
            if not (degraded or shed):
                # A degraded run satisfied the RELAXED bound, a shed run
                # only its measured pilot bar -- neither may teach the
                # cache an answer keyed on the requested epsilon.
                self._cache_insert(
                    entry, beta=r.beta, n=r.n, theta=r.theta, error=err,
                    success=bool(r.success), failed=bool(r.failed),
                    iterations=its,
                    group_error=r.error if grouped else None,
                    group_success=r.group_success if grouped else None)
            wall = now - entry.ticket.submitted_s
            resident = r.wall_time_s - r.queue_wait_s
            self._complete(
                entry, theta=r.theta, error=err, success=bool(r.success),
                n=r.n, wall_time_s=wall,
                queue_wait_s=max(wall - resident, 0.0),
                route=Route.WARM if warm else Route.POOL,
                rows_sampled=r.rows_sampled, now=now,
                group_error=np.asarray(r.error) if grouped else None,
                group_success=(np.asarray(r.group_success) if grouped
                               else None),
                delivered_epsilon=getattr(r, "delivered_epsilon", None),
                delivered_B=getattr(r, "delivered_B", None),
                degraded=degraded, shed=shed)

    # -- synchronous routes -------------------------------------------------
    def _group_scale(self, func: str, k: int):
        """(k, m) per-lane scale rows for one func (SS2.2.1 transform)."""
        row = jnp.asarray(
            estimators.population_scale_row(func, self.data.scale))
        return jnp.broadcast_to(row, (k, self._m))

    def _dispatch_fused(self, func: str, queries: List[Query], keys):
        """One batched fused program for ``len(queries)`` same-func lanes."""
        k = len(queries)
        eps = jnp.asarray([q.epsilon for q in queries], jnp.float32)
        deltas = jnp.asarray([q.delta for q in queries], jnp.float32)
        res = fused_l2miss_batch(
            self.data.values, self._offsets,
            self._group_scale(func, k), jnp.stack(keys), eps,
            deltas, sample_keys=self._sample_key,
            est_name=func, B=self.B, n_min=self.n_min, n_max=self.n_max,
            l=min(self._m + 2, 12), max_iters=self.max_iters,
            n_cap=self.n_cap, use_kernel=self.use_kernel)
        self.fused_dispatches += 1
        return res

    def _by_func(self, entries: List[_InFlight]
                 ) -> List[Tuple[str, List[_InFlight]]]:
        by_func: Dict[str, List[_InFlight]] = {}
        for e in entries:
            by_func.setdefault(e.request.query.func, []).append(e)
        return list(by_func.items())

    def _run_batched(self, entries: List[_InFlight]) -> None:
        """Phase-C closed-loop batching: ONE dispatch per func group;
        amortized per-query wall time (dispatch / lane count -- per-lane
        wall clock inside one program is not observable)."""
        for func, group in self._by_func(entries):
            keys = self._lane_keys(group)
            t0 = time.perf_counter()
            res = self._dispatch_fused(
                func, [e.request.query for e in group], keys)
            theta, errs, succ, ns, rows, betas, fails, its = \
                self.recorder.device_get(
                    (res.theta, res.error, res.success, res.n,
                     res.rows_sampled, res.beta, res.failed, res.iterations))
            per_q = (time.perf_counter() - t0) / len(group)
            for lane, e in enumerate(group):
                self._fused_rows += int(rows[lane])
                self._cache_insert(
                    e, beta=betas[lane], n=ns[lane], theta=theta[lane],
                    error=float(errs[lane]), success=bool(succ[lane]),
                    failed=bool(fails[lane]), iterations=int(its[lane]))
                self._complete(
                    e, theta=theta[lane], error=float(errs[lane]),
                    success=bool(succ[lane]), n=ns[lane],
                    wall_time_s=per_q, queue_wait_s=0.0,
                    route=Route.BATCHED, rows_sampled=int(rows[lane]))

    def _run_loop(self, entries: List[_InFlight]) -> None:
        """Per-query dispatch loop: k dispatches, timed individually."""
        for func, group in self._by_func(entries):
            keys = self._lane_keys(group)
            for e, key in zip(group, keys):
                t0 = time.perf_counter()
                res = self._dispatch_fused(func, [e.request.query], [key])
                theta, err, succ, n, rows, beta, failed, its = \
                    self.recorder.device_get(
                        (res.theta, res.error, res.success, res.n,
                         res.rows_sampled, res.beta, res.failed,
                         res.iterations))
                rows = int(rows[0])
                self._fused_rows += rows
                self._cache_insert(
                    e, beta=beta[0], n=n[0], theta=theta[0],
                    error=float(err[0]), success=bool(succ[0]),
                    failed=bool(failed[0]), iterations=int(its[0]))
                self._complete(
                    e, theta=theta[0], error=float(err[0]),
                    success=bool(succ[0]), n=n[0],
                    wall_time_s=time.perf_counter() - t0, queue_wait_s=0.0,
                    route=Route.LOOP, rows_sampled=rows)

    def _run_host(self, entry: _InFlight) -> None:
        """Host-engine fallback (order/diff/lp/linf/predicates/relative
        bounds/quantiles; grouped queries a pool block cannot serve --
        predicates, relative bounds, sharded layouts)."""
        t0 = time.perf_counter()
        if entry.request.query.group_by:
            return self._run_host_grouped(entry, t0)
        tr = self.engine.execute(entry.request.query)
        beta = tr.info.get("beta") if isinstance(tr.info, dict) else None
        self._cache_insert(
            entry, beta=beta, n=tr.n, theta=tr.theta, error=tr.error,
            success=bool(tr.success), failed=tr.status == "unrecoverable",
            iterations=int(tr.iterations))
        self._complete(
            entry, theta=tr.theta, error=tr.error, success=tr.success,
            n=tr.n, wall_time_s=time.perf_counter() - t0, queue_wait_s=0.0,
            route=Route.HOST, rows_sampled=0)

    def _run_host_grouped(self, entry: _InFlight, t0: float) -> None:
        """Engine-side grouped execution (``AQPEngine.execute_grouped``):
        the same shared-scan block program, dispatched synchronously
        outside the pool.  Serves grouped clauses the pool block cannot
        (predicates fold into the measure, relative bounds resolve against
        the pilot) and every grouped request of a sharded session."""
        res = self.engine.execute(entry.request.query)
        theta, gerr, gok, n, rows, beta, failed, its = \
            self.recorder.device_get(
                (res.theta, res.error, res.success, res.n, res.rows_sampled,
                 res.beta, res.failed, res.iterations))
        theta = np.asarray(theta)[:, 0]
        gerr, gok, n = np.asarray(gerr), np.asarray(gok), np.asarray(n)
        rows = int(np.asarray(rows).sum())
        self._fused_rows += rows
        self.fused_dispatches += 1
        self._cache_insert(
            entry, beta=np.asarray(beta), n=n, theta=theta,
            error=float(gerr.max()), success=bool(gok.all()),
            failed=bool(np.asarray(failed).any()),
            iterations=int(np.asarray(its).max()),
            group_error=gerr, group_success=gok)
        self._complete(
            entry, theta=theta, error=float(gerr.max()),
            success=bool(gok.all()), n=n,
            wall_time_s=time.perf_counter() - t0, queue_wait_s=0.0,
            route=Route.HOST, rows_sampled=rows,
            group_error=gerr, group_success=gok)
