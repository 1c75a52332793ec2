"""Continuous lane-pool AQP serving (DESIGN.md SS7 phases D + E).

A closed ``while_loop`` over a batch of lanes (``fused_l2miss_lanes``) keeps
converged lanes frozen-but-resident until the slowest lane finishes, so
under mixed-epsilon traffic most of the program's lane-ticks are spent on
already-answered queries.  This module ports the seed repo's continuous-
batching pattern (serve/batching.py: lockstep decode slots with splice-in
refill) to AQP: a FIXED pool of ``lanes`` query lanes is ticked from the
host via the resumable :func:`~repro.core.fused.fused_step`, and between
ticks converged lanes are RETIRED (answer harvested) and REFILLED by
splicing a waiting query's (scale, key, epsilon, delta, estimator) into the
freed lane -- one resident XLA program serves an unbounded query stream.

Why retire/refill preserves trajectories (the counter-PRNG nesting):

  * a lane's tick counter ``k`` is per-lane state; the splice resets it to
    0, so the refilled lane replays the exact init schedule a fresh run
    would;
  * the bootstrap stream is ``hash3(boot_base(key), k, group)`` -- a pure
    function of the lane's OWN key and age, never of its neighbors or of
    wall-clock tick count;
  * the slot->row binding is the pool-shared ``sample_key`` table
    (``sampling.counter_slot_table``), so every occupant of every lane
    extends the same permuted prefixes (SS3.2 reuse), and a refilled lane
    gathers exactly the rows a solo run with that ``sample_key`` would;
  * the ESTIMATE width bucket is the max watermark over active lanes --
    compute width only; the counter-PRNG draws are width-invariant.

Width-aware admission (phase E): the shared ESTIMATE bucket makes lane
PLACEMENT a cost decision -- a fresh ``n_min`` lane spliced next to a wide
straggler rides at the straggler's bucket even though its own watermark
needs the narrowest one.  The pool therefore splits its lanes into
``tiers`` equal sub-pools, each with its own ``LaneState``/``LaneParams``
and its own per-tier dispatch (equal shapes, so every tier shares ONE
compiled step program), and admission places each waiting query into the
free-laned tier with the SMALLEST active watermark.  Stragglers pile up in
the wide tier; fresh queries ride narrow buckets next to other young
lanes.  Placement is best-effort: when only a wide tier has a free lane
the query is admitted there rather than held back (capacity is never
hostage to the cost model), and per-lane trajectories are tier-invariant
(the bucket is compute width only), so tiering changes cost, never
answers.

Heterogeneity: lanes select their estimator per-lane by moment-family index
(``est_name=None`` routing through ``estimate_error_lanes_het``), so
mean/sum/count/std/var/proportion queries share ONE pool instead of one
dispatch per func group.  SUM/COUNT lanes carry their population scale in
their ``LaneParams.scale`` row.

Accounting: per-query latency is measured submit -> harvest (real, not
amortized), queue wait separately; ``stats()`` exposes tick/dispatch
counts, lane occupancy, backpressure (peak queue depth), the per-dispatch
active-lane fraction, and the gathered-rows-per-tick rate -- the two
observables of the phase-E gating (kernel tiles and window gathers both
scale with active lanes, not pool width).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..aqp.query import Query
from ..core import bootstrap
from ..core import mesh as core_mesh
from ..core.fused import (LaneParams, LaneState, ShardSpec, as_columns,
                          bucket_ladder, fused_step, grouped_seg_cap,
                          init_lane_state, lane_boot_seed,
                          make_group_lane_params,
                          make_lane_params, make_shard_spec,
                          make_sharded_lane_params, make_sharded_step,
                          resolve_ext_cap, resolve_seg_window, scoped,
                          sharded_psum_bytes, sharded_step_cache_size)
from ..core import estimators
from ..core import sanitize
from ..core.sampling import (GroupedData, ShardLayout, counter_slot_table,
                             stratified_slot_tables)
from ..kernels import resolve_use_kernel
from .slo import (PILOT_B_FLOOR, AdmissionController, FairQueue,
                  predict_n0)
from .tracing import PhaseRecorder

Array = jax.Array


@dataclasses.dataclass
class PoolResponse:
    """One retired query: the answer plus the pool's latency accounting."""
    qid: int
    func: str
    theta: np.ndarray       # (m, 1) scaled estimate
    error: float
    success: bool           # error bound met
    failed: bool            # Algorithm-2 unrecoverable failure
    n: np.ndarray           # (m,) final sizes
    iterations: int
    rows_sampled: int       # final filled watermark (shared-prefix rows)
    wall_time_s: float      # submit -> harvest
    queue_wait_s: float     # submit -> splice
    ticks_in_lane: int      # loop ticks while resident
    lane: int               # global lane id (tier * tier_lanes + local)
    tier: int               # width tier the query rode in (-1: shed, no lane)
    spliced_tier_width: int  # tier's max active watermark at splice time
    beta: Optional[np.ndarray] = None   # (m+1,) final fitted coefficients
    warm: bool = False      # lane was warm-started from a cached prediction
    # Phase J (overload-native scheduling): the delivered contract.  A
    # degraded answer ran at ``delivered_epsilon > epsilon`` (relaxed along
    # Eq. 13 to fit the deadline); a shed answer is an n_min pilot whose
    # ``delivered_epsilon`` is its MEASURED bootstrap quantile.  Either way
    # ``error <= delivered_epsilon`` holds -- degradation trades the bound,
    # never the correctness of the bound it reports.
    epsilon: Optional[float] = None           # requested bound
    delivered_epsilon: Optional[float] = None  # bound actually satisfied
    delivered_B: Optional[int] = None          # replicate count actually run
    degraded: bool = False   # epsilon was relaxed at admission
    shed: bool = False       # answered by pilot, never occupied a lane
    migrations: int = 0      # cross-tier migrations while resident
    tenant: str = ""         # fair-queueing traffic class


@dataclasses.dataclass
class GroupPoolResponse:
    """One retired GROUP BY query: per-group answers plus accounting.

    A grouped query occupies a lane BLOCK (G per-group lanes ticked as one
    shared-scan unit -- DESIGN.md phase I), so its response carries one
    answer and one ``(epsilon, delta)`` verdict PER GROUP.  ``success`` is
    the conjunction over groups; ``error`` the (G,) per-group quantiles.
    """
    qid: int
    func: str
    theta: np.ndarray        # (G,) scaled per-group estimates
    error: np.ndarray        # (G,) per-group error quantiles
    group_success: np.ndarray  # (G,) per-group verdicts
    success: bool            # every group met its bound
    failed: bool             # any group hit an Algorithm-2 failure
    n: np.ndarray            # (G,) final per-group sizes
    iterations: np.ndarray   # (G,) per-group iteration counts
    rows_sampled: int        # sum of per-group filled watermarks
    wall_time_s: float       # submit -> harvest
    queue_wait_s: float      # 0.0: blocks admit atomically at submit
    ticks_in_block: int      # loop ticks while resident
    beta: Optional[np.ndarray] = None   # (G, 2) per-group coefficients
    warm: bool = False       # block was warm-started per group
    group_by: bool = True    # discriminates from PoolResponse at harvest


@dataclasses.dataclass
class _Block:
    """One resident grouped block: its own carry/params, ticked whole."""
    qid: int
    func: str
    state: LaneState         # q = G lanes of m = 1
    params: LaneParams
    submitted_s: float
    admitted_tick: int
    warm: bool = False


@dataclasses.dataclass
class _Ticket:
    qid: int
    func: str
    fid: int
    epsilon: float
    delta: float
    key: np.ndarray
    scale_row: np.ndarray
    submitted_s: float
    priority: int = 0                       # higher = admitted first
    deadline_at: Optional[float] = None     # absolute perf_counter deadline
    warm_n0: Optional[np.ndarray] = None    # (m,) cached n* prediction
    warm_beta: Optional[np.ndarray] = None  # (m+1,) cached coefficients
    tenant: str = ""                        # fair-queueing traffic class
    vft: float = 0.0                        # WFQ virtual finish time
    delivered_epsilon: Optional[float] = None  # set when degraded
    degraded: bool = False
    migrations: int = 0                     # cross-tier moves while resident
    spliced_s: float = 0.0
    spliced_tick: int = 0
    spliced_width: int = 0

    @property
    def order(self):
        """Admission order: priority class first, then weighted-fair
        virtual finish time, then earliest deadline, then FIFO.  With fair
        queueing off every ticket's ``vft`` is 0.0, so the order reduces
        exactly to the phase-E (priority, deadline, FIFO) scan; with it on,
        each tenant's backlog advances its own virtual clock
        (``slo.FairQueue``), so a burst from one tenant cannot starve the
        others.  Ordering changes WHEN a query is spliced, never its
        trajectory (a lane's draws depend only on its own key and age)."""
        ddl = self.deadline_at if self.deadline_at is not None else np.inf
        return (-self.priority, self.vft, ddl, self.qid)

    @property
    def eps_run(self) -> float:
        """The bound the lane actually runs at (degraded or requested)."""
        return (self.delivered_epsilon if self.delivered_epsilon is not None
                else self.epsilon)


@dataclasses.dataclass
class _Tier:
    """One width tier: its own carry/params and occupancy bookkeeping."""
    state: LaneState
    params: LaneParams
    occupant: List[Optional[_Ticket]]
    filled_host: np.ndarray     # (tier_lanes, m) watermarks at last sync

    @property
    def busy(self) -> int:
        return sum(t is not None for t in self.occupant)

    @property
    def width(self) -> int:
        """Max watermark over OCCUPIED lanes -- the bucket driver a fresh
        splice would share.  Lags one sync (host cache); a just-spliced
        lane counts as 0, which is exactly its watermark."""
        occ = [i for i, t in enumerate(self.occupant) if t is not None]
        return int(self.filled_host[occ].max()) if occ else 0


@partial(jax.jit, static_argnames=("n_min",))
@scoped("miss.splice")
def _splice(state: LaneState, params: LaneParams, lanes, keys, scale_rows,
            eps, deltas, fids, warm, warm_n0, warm_beta, *, n_min: int):
    """Reset lanes ``lanes`` to tick 0, swapping in their new queries.

    One dispatch splices a whole refill round: the row arrays are padded to
    tier width with out-of-range lane indices, which ``mode="drop"``
    discards -- so every round shares ONE compiled splice regardless of how
    many lanes freed up (tiers have equal lane counts, so all tiers share
    it too).  The jit matters doubly under a mesh: un-jitted, each of the
    ~19 leaf updates is its own SPMD launch across every device; jitted,
    the whole splice is one program and sharding propagation keeps ``buf``
    resident where it was (the slot axis never moves).  Must reproduce
    ``init_lane_state`` / ``make_lane_params`` row-for-row so a refilled
    lane is indistinguishable from lane i of a fresh pool -- the refill
    invariant the parity tests assert.
    """
    drop = dict(mode="drop")
    st = state._replace(
        keys=state.keys.at[lanes].set(keys, **drop),
        k=state.k.at[lanes].set(0, **drop),
        iters=state.iters.at[lanes].set(0, **drop),
        n_cur=state.n_cur.at[lanes].set(n_min, **drop),
        filled=state.filled.at[lanes].set(0, **drop),
        buf=state.buf.at[lanes].set(0.0, **drop),
        prof_n=state.prof_n.at[lanes].set(1.0, **drop),
        prof_loge=state.prof_loge.at[lanes].set(0.0, **drop),
        e=state.e.at[lanes].set(jnp.inf, **drop),
        theta=state.theta.at[lanes].set(0.0, **drop),
        done=state.done.at[lanes].set(False, **drop),
        failed=state.failed.at[lanes].set(False, **drop),
        beta=state.beta.at[lanes].set(0.0, **drop),
        r2=state.r2.at[lanes].set(0.0, **drop),
    )
    pr = params._replace(
        scale=params.scale.at[lanes].set(scale_rows, **drop),
        epsilons=params.epsilons.at[lanes].set(eps, **drop),
        deltas=params.deltas.at[lanes].set(deltas, **drop),
        est_fids=params.est_fids.at[lanes].set(fids, **drop),
        boot_base=params.boot_base.at[lanes].set(
            jax.vmap(lane_boot_seed)(keys), **drop),
        warm=params.warm.at[lanes].set(warm, **drop),
        warm_n0=params.warm_n0.at[lanes].set(warm_n0, **drop),
        warm_beta=params.warm_beta.at[lanes].set(warm_beta, **drop),
    )
    return st, pr


# The per-lane rows a cross-tier migration must carry: every LaneState leaf
# (the whole MISS trajectory: buffer, profile, fit, flags) plus the
# per-lane LaneParams rows _splice swaps.  ``slot_idx`` / ``group_sizes``
# are POOL-shared (every tier is built from the same sample key), so the
# moved lane rebinds to an identical table -- which is why a migrated
# trajectory is bit-equal to its solo run: the lane's draws depend only on
# its own rows, and the ESTIMATE bucket it rides is compute width only
# (width invariance is asserted bitwise in tests/test_core_fused_buckets).
_STATE_LEAVES = ("keys", "k", "iters", "n_cur", "filled", "buf", "prof_n",
                 "prof_loge", "e", "theta", "done", "failed", "beta", "r2")
_PARAM_LANE_LEAVES = ("scale", "epsilons", "deltas", "est_fids", "boot_base",
                      "warm", "warm_n0", "warm_beta")


@jax.jit
def _migrate(src_st: LaneState, src_pr: LaneParams, dst_st: LaneState,
             dst_pr: LaneParams, src_lane, dst_lane):
    """Splice lane ``src_lane`` of one tier into ``dst_lane`` of another,
    mid-flight: row-copy the full carry (phase-J cross-tier migration) and
    park the source lane as done.  One jitted program for the whole move,
    shared by every (tier, tier) pair -- equal tier shapes."""
    st = dst_st._replace(**{
        f: getattr(dst_st, f).at[dst_lane].set(getattr(src_st, f)[src_lane])
        for f in _STATE_LEAVES})
    pr = dst_pr._replace(**{
        f: getattr(dst_pr, f).at[dst_lane].set(getattr(src_pr, f)[src_lane])
        for f in _PARAM_LANE_LEAVES})
    parked = src_st._replace(done=src_st.done.at[src_lane].set(True))
    return parked, st, pr


@partial(jax.jit, static_argnames=("est_name", "B", "metric"))
def _pilot_estimate(values, slot_tab, sizes, scale_row, key, delta, *,
                    est_name: str, B: int, metric: str):
    """The shed path's answer: one n_min-wide stratified pilot ESTIMATE.

    Gathers each group's pilot prefix through its own counter slot table
    (the same permuted-prefix contract resident lanes use) and returns the
    measured ``(1 - delta)`` bootstrap quantile plus the point estimate --
    a real answer with a real (wide) error bar, at the cost of ONE tiny
    dispatch instead of a lane residency.
    """
    est = estimators.get(est_name)
    n_pilot = slot_tab.shape[1]
    sample = values[slot_tab]                               # (m, n_pilot, c)
    mask = (jnp.arange(n_pilot, dtype=jnp.int32)[None, :]
            < jnp.minimum(sizes, n_pilot)[:, None]).astype(jnp.float32)
    return bootstrap.estimate_error(
        est, sample, mask, scale_row, key, delta, B=B, metric=metric)


class LanePool:
    """A fixed pool of query lanes with width-aware admission and
    retire-and-refill.

    One resident program: all tiers share ONE compiled ``fused_step``
    signature (equal tier shapes) and every query -- any moment-family
    estimator, any (epsilon, delta) -- runs through it.  ``ticks_per_sync``
    trades host round-trips against refill granularity: converged lanes
    freeze natively inside a multi-tick dispatch (predicated updates), they
    just aren't refilled until the next sync.  ``tiers="auto"`` splits any
    even pool into two width tiers; ``tiers=1`` restores the flat pool.
    """

    def __init__(self, data: GroupedData, *, lanes: int = 4, B: int = 300,
                 n_min: int = 1000, n_max: int = 2000, max_iters: int = 24,
                 n_cap: int = 1 << 16, l: Optional[int] = None,
                 metric: str = "l2", growth_cap: float = 8.0,
                 ext_cap: Optional[int] = None,
                 use_kernel: "bool | str" = "auto",
                 gate_gather: bool = True, seed: int = 0,
                 sample_key: Optional[Array] = None,
                 ticks_per_sync: int = 1, tiers: "int | str" = "auto",
                 data_shards: int = 1, mesh=None,
                 degrade: bool = False, wfq: bool = False,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 migrate: bool = False, max_degrade: float = 8.0,
                 recorder: Optional[PhaseRecorder] = None):
        self.data = data
        self.lanes = int(lanes)
        # Phase spans and counters: the owning session's recorder, so they
        # survive pool rebuilds; a pool built alone keeps its own.
        self.recorder = recorder if recorder is not None else PhaseRecorder()
        use_kernel = resolve_use_kernel(use_kernel)
        if tiers == "auto":
            tiers = 2 if self.lanes >= 2 and self.lanes % 2 == 0 else 1
        self.tiers = int(tiers)
        if self.lanes % self.tiers:
            raise ValueError(
                f"lanes ({self.lanes}) must divide evenly into tiers "
                f"({self.tiers})")
        self.tier_lanes = self.lanes // self.tiers
        m = data.num_groups
        self.data_shards = int(data_shards)
        self._offsets = jnp.asarray(data.offsets)
        self._family = {e.name: i
                        for i, e in enumerate(estimators.moment_family())}
        if self.data_shards > 1:
            # Phase G: values row-sharded over the mesh, buffers segmented
            # over the slot axis, one compiled shard_map step per num_ticks.
            # ``mesh=False`` keeps the SAME shard layout on one device (the
            # solo-emulation ``fused_step`` path) -- the bitwise reference a
            # mesh pool's answers are checked against.
            self._layout = ShardLayout.build(
                np.asarray(data.offsets), n_cap=n_cap,
                num_shards=self.data_shards)
            if mesh is False:
                self._mesh = None
            else:
                self._mesh = mesh if mesh is not None else (
                    core_mesh.table_mesh(data.values, self.data_shards))
                if self._mesh.devices.size != self.data_shards:
                    raise ValueError(
                        f"mesh has {self._mesh.devices.size} devices; pool "
                        f"wants data_shards={self.data_shards}")
            rows = self.data_shards * self._layout.rows_per_shard
            with self.recorder.phase("table_layout"):
                if self._mesh is None:
                    padded = core_mesh.pad_rows(data.values, rows)
                    self._values = jnp.asarray(padded)
                    self.table_host_bytes = int(padded.nbytes)
                    self._cols = as_columns(self._values)
                else:
                    # The session's table, made row-sharded in the layout's
                    # blocks, is taken as it is, and each device writes its
                    # own block of each column from it: no device ever
                    # holds more than its own blocks.
                    self._values, self.table_host_bytes = \
                        core_mesh.put_row_blocks(self._mesh, data.values,
                                                 rows)
                    self._cols = as_columns(
                        self._values, core_mesh.data_sharding(self._mesh))
            sspec = make_shard_spec(self._layout)
            if self._mesh is not None:
                sspec = ShardSpec(
                    alloc=core_mesh.put_replicated(self._mesh, sspec.alloc),
                    cap_groups=core_mesh.put_replicated(
                        self._mesh, sspec.cap_groups))
            self._shard_spec = sspec
            self._spec = dict(
                est_name=None, B=B, n_min=n_min, n_max=n_max,
                l=int(l if l is not None else min(m + 2, 12)), tau=1e-3,
                max_iters=max_iters, n_cap=n_cap, metric=metric,
                growth_cap=growth_cap,
                seg_window=resolve_seg_window(n_cap, n_max, self.data_shards,
                                              ext_cap),
                use_kernel=use_kernel, data_shards=self.data_shards)
            self._step_cache: Dict[int, object] = {}
        else:
            self._layout = None
            self._mesh = None
            # Built once per pool: the step reads 1-D columns, so no tick
            # relayouts the table.
            with self.recorder.phase("table_layout"):
                self._values = self._cols = as_columns(data.values)
            self.table_host_bytes = 0
            self._spec = dict(
                est_name=None, B=B, n_min=n_min, n_max=n_max,
                l=int(l if l is not None else min(m + 2, 12)), tau=1e-3,
                max_iters=max_iters, n_cap=n_cap, backend="poisson",
                metric=metric, growth_cap=growth_cap,
                ext_cap=resolve_ext_cap(n_cap, n_max, ext_cap), adaptive=True,
                use_kernel=use_kernel, gate_gather=gate_gather)
        # Bytes the pool holds of the table on each device (the table and
        # its columns, where they are not the same arrays), and bytes each
        # chip adds to the per-tick moment psums
        # (``fused.sharded_psum_bytes``) summed over ticks; 0 off the mesh.
        self.table_bytes_by_device: Dict[int, int] = {}
        held = {id(a): a for a in jax.tree_util.tree_leaves(
            (self._values, self._cols))}
        for col in held.values():
            for sh in col.addressable_shards:
                self.table_bytes_by_device[sh.device.id] = (
                    self.table_bytes_by_device.get(sh.device.id, 0)
                    + int(sh.data.nbytes))
        self._psum_tick_bytes = (
            sharded_psum_bytes(self.tier_lanes, m, B)
            if self._mesh is not None else 0)
        self.psum_bytes = 0
        # Steady-state recompile sentinel (misslint ML30x at runtime): a
        # snapshot of the resident-program cache, re-armed whenever a NEW
        # program config legitimately enters (retuned cadence, a fresh
        # tier/block warming up).  Growth between two ticks with no such
        # event is a recompile in the dispatch hot path.
        self.steady_recompiles = 0
        self._steady_cache0: Optional[int] = None
        self._warmed_tiers: set = set()
        self.ticks_per_sync = int(ticks_per_sync)
        self.key = jax.random.PRNGKey(seed)
        if sample_key is None:
            sample_key = jax.random.PRNGKey(seed ^ 0x5A17)
        self._sample_key = jnp.asarray(sample_key)
        keys0 = jax.random.split(jax.random.PRNGKey(seed), self.lanes)
        tl = self.tier_lanes
        self._tiers: List[_Tier] = []
        for ti in range(self.tiers):
            tkeys = keys0[ti * tl:(ti + 1) * tl]
            if self.data_shards > 1:
                params = make_sharded_lane_params(
                    self._layout, jnp.ones((tl, m), jnp.float32), tkeys,
                    jnp.ones((tl,), jnp.float32),
                    jnp.full((tl,), 0.05, jnp.float32),
                    self._sample_key, jnp.zeros((tl,), jnp.int32),
                    local_rows=self._mesh is not None)
                if self._mesh is not None:
                    params = params._replace(slot_idx=core_mesh.put_sharded(
                        self._mesh, params.slot_idx))
            else:
                params = make_lane_params(
                    self._offsets, jnp.ones((tl, m), jnp.float32), tkeys,
                    jnp.ones((tl,), jnp.float32),
                    jnp.full((tl,), 0.05, jnp.float32),
                    self._sample_key, jnp.zeros((tl,), jnp.int32),
                    n_cap=n_cap)
            state = init_lane_state(
                tkeys, m, n_cap=n_cap, c_dim=data.values.shape[1], p_dim=1,
                n_min=n_min, max_iters=max_iters, dtype=data.values.dtype)
            if self.data_shards > 1 and self._mesh is not None:
                state = jax.tree_util.tree_map(
                    lambda x: core_mesh.put_replicated(self._mesh, x), state)
                state = state._replace(buf=jax.device_put(
                    state.buf, core_mesh.data_sharding(self._mesh, 4, 2)))
            # Empty lanes are parked as ``done``: the step freezes them
            # (gated bootstrap AND gated gather -- phase E) until a splice
            # brings them live.
            self._tiers.append(_Tier(
                state=state._replace(done=jnp.ones((tl,), bool)),
                params=params, occupant=[None] * tl,
                filled_host=np.zeros((tl, m), np.int64)))
        self._queue: Deque[_Ticket] = deque()
        # Phase I: resident grouped blocks (G per-group lanes each, ticked
        # as one shared-scan unit).  Admission is atomic -- a block never
        # waits in the ticket queue -- and every block of this pool shares
        # one compiled step signature (q = num_groups, m = 1, one seg_cap).
        self._blocks: Dict[int, _Block] = {}
        self._gseg_cap = (grouped_seg_cap(np.asarray(data.offsets), n_cap)
                          if self.data_shards == 1 else 0)
        # The grouped step's dummy offsets: a block's slot tables already
        # hold GLOBAL row indices, so its step sees one [0, N) span.
        self._goffsets = jnp.asarray(
            [0, int(np.asarray(data.offsets)[-1])], jnp.int32)
        self._gtables: Optional[Array] = None   # stratified tables, per epoch
        # (state, params) shapes of this pool's blocks, once one is admitted.
        self._block_shapes = None
        self._pending_sample_key: Optional[Array] = None
        self.sample_epochs = 0    # applied slot-table rotations
        self._scale_rows: Dict[str, np.ndarray] = {}
        # Hand-off buffer: harvest fills it, drain() pops it.  Never grows
        # past the queries in flight plus uncollected retirees.
        self.results: Dict[int, PoolResponse] = {}
        self._next_qid = 0
        # Scheduling / backpressure accounting.
        self.ticks = 0            # scheduling rounds executed
        self.dispatches = 0       # step program launches (tier syncs)
        self.lane_ticks_busy = 0  # occupied-lane ticks (occupancy integral)
        self.submitted = 0
        self.retired = 0
        self.grouped_submitted = 0   # blocks admitted (phase I)
        self.grouped_retired = 0     # blocks harvested
        self.block_ticks = 0         # block-resident loop ticks
        self.warm_spliced = 0     # warm-started lanes admitted (phase H)
        # Phase J: overload-native scheduling.  ``degrade`` arms the
        # deadline-driven admission controller (relax epsilon along Eq. 13
        # when the predicted cost misses the deadline; shed with a pilot
        # answer when it is already blown); ``wfq`` arms per-tenant
        # weighted fair queueing; ``migrate`` arms cross-tier lane
        # migration (tiers >= 2, single-device layout only: a sharded
        # pool's tiers cover SEGMENT fills).  All default off -- the
        # phase-E pool is the exact special case.
        self.degrade_enabled = bool(degrade)
        self._slo = AdmissionController(
            bucket_ladder(self._spec["n_cap"], self._spec["n_max"]),
            num_groups=m, n_min=self._spec["n_min"],
            max_degrade=max_degrade) if degrade else None
        self._wfq = FairQueue(tenant_weights) if wfq else None
        self.migrate_enabled = (bool(migrate) and self.tiers >= 2
                                and self.data_shards == 1)
        self.shed = 0             # requests answered by pilot, never laned
        self.degraded = 0         # requests admitted at a relaxed epsilon
        self.migrations = 0       # cross-tier lane moves
        self._group_sizes_host = np.diff(
            np.asarray(data.offsets)).astype(np.int64)
        self._pilot_tab: Optional[Array] = None   # per-epoch pilot tables
        self._pilot_values: Optional[Array] = None
        self.peak_queue_depth = 0
        self._active_frac_sum = 0.0   # sum over dispatches of busy/tier_lanes
        self._retired_rows = 0        # rows_sampled of retired queries
        # Per-shard slot residency of retired queries (phase G dispatch
        # accounting; a single-device pool reports one shard).
        self._shard_rows_retired = np.zeros(
            (max(self.data_shards, 1),), np.int64)

    # -- admission ----------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def busy_lanes(self) -> int:
        return sum(t.busy for t in self._tiers)

    @property
    def busy_blocks(self) -> int:
        return len(self._blocks)

    def supports_grouped(self, query: Query) -> bool:
        """Whether this pool can serve ``query`` as a grouped lane block
        (same clause constraints as :meth:`supports`; blocks additionally
        need the single-device layout -- the packed shared scan is not
        mesh-sharded)."""
        return self.data_shards == 1 and self.supports(query)

    def supports(self, query: Query) -> bool:
        """Whether this pool can serve ``query`` (moment family, this
        metric, absolute bound, no predicate)."""
        return (query.func in self._family
                and query.metric == self._spec["metric"]
                and query.epsilon is not None
                and query.predicate is None)

    def submit(self, query: Query, key: Optional[Array] = None, *,
               priority: int = 0,
               deadline_at: Optional[float] = None,
               warm_n0: Optional[np.ndarray] = None,
               warm_beta: Optional[np.ndarray] = None,
               tenant: str = "") -> int:
        """Enqueue one query; returns its qid (results keyed on it).

        ``priority`` / ``deadline_at`` (an absolute ``time.perf_counter``
        timestamp) shape ADMISSION ordering only -- higher priority first,
        then earliest deadline, then FIFO; see ``_Ticket.order``.  With
        ``wfq=True`` the scan inserts the tenant's weighted-fair virtual
        finish time between priority and deadline; with ``degrade=True`` a
        deadline already blown at submit is shed HERE -- the pilot answer
        lands in :attr:`results` before this call returns, and the queue
        never sees the ticket.

        ``warm_n0``/``warm_beta`` (phase H, both or neither) splice the
        query as a WARM lane: tick 0 jumps to the cached prediction and
        the lane verifies instead of walking the init design.  Warm lanes
        land in the narrowest free tier like every young lane (the
        width-aware ``_place_tier`` already prefers it -- their watermark
        is 0 at splice and small by construction after).
        """
        if (warm_n0 is None) != (warm_beta is None):
            raise ValueError("warm_n0 and warm_beta come together")
        if not self.supports(query):
            raise ValueError(
                f"lane pool cannot serve func={query.func!r} "
                f"metric={query.metric!r} (supported funcs: "
                f"{sorted(self._family)}, metric {self._spec['metric']!r}, "
                f"absolute epsilon, no predicate)")
        if key is None:
            self.key, key = jax.random.split(self.key)
        scale_row = self._scale_rows.get(query.func)
        if scale_row is None:
            scale_row = estimators.population_scale_row(
                query.func, self.data.scale)
            self._scale_rows[query.func] = scale_row
        qid = self._next_qid
        self._next_qid += 1
        self.submitted += 1
        m = self.data.num_groups
        if warm_n0 is not None:
            # The step clips n to group sizes / n_cap anyway; clamping here
            # keeps the int32 device row safe from oversized predictions.
            warm_n0 = np.clip(
                np.asarray(warm_n0, np.int64).reshape((m,)),
                1, self._spec["n_cap"]).astype(np.int32)
            warm_beta = np.asarray(warm_beta, np.float32).reshape((m + 1,))
        vft = 0.0
        if self._wfq is not None:
            # The WFQ cost quantum is the predicted watermark -- rows a
            # lane will hold, the resource tenants actually contend for.
            # Falls back to n_min (every lane's floor) while unprimed.
            wm = None
            if self._slo is not None:
                wm = self._slo.cost.predict_watermark(
                    query.func, float(query.epsilon), warm_n0=warm_n0)
            if wm is None:
                wm = (int(np.max(warm_n0)) if warm_n0 is not None
                      else self._spec["n_min"])
            vft = self._wfq.stamp(tenant, float(wm))
        tk = _Ticket(
            qid=qid, func=query.func, fid=self._family[query.func],
            epsilon=float(query.epsilon), delta=float(query.delta),
            key=self.recorder.device_get(key), scale_row=scale_row,
            submitted_s=time.perf_counter(),
            priority=int(priority), deadline_at=deadline_at,
            warm_n0=warm_n0, warm_beta=warm_beta,
            tenant=str(tenant), vft=vft)
        if self._slo is not None and deadline_at is not None:
            # Shed at SUBMIT, not just when already blown: once the
            # predicted queue wait plus the CHEAPEST degraded service
            # exceeds the budget, queueing only converts a fast partial
            # answer into a late one.  An unprimed cost model never
            # predicts hopeless -- the ticket queues and we find out.
            if (deadline_at <= tk.submitted_s
                    or self._slo.hopeless(
                        queue_ahead=len(self._queue),
                        busy=self.busy_lanes, lanes=self.lanes,
                        deadline_at=deadline_at, now=tk.submitted_s)):
                self._shed(tk, tk.submitted_s, blown=True)
                return qid
        self._queue.append(tk)
        self.peak_queue_depth = max(self.peak_queue_depth, len(self._queue))
        return qid

    def _grouped_tables(self) -> Array:
        """The stratified per-group slot tables under the CURRENT sample
        key, built once per epoch and shared by every block admitted in it
        (rotation invalidates the cache; it only fires with no blocks
        resident, so no live block ever sees two bindings)."""
        if self._gtables is None:
            self._gtables = stratified_slot_tables(
                self._sample_key, self._offsets, self._spec["n_cap"])
        return self._gtables

    def submit_group(self, query: Query, key: Optional[Array] = None, *,
                     warm_n0: Optional[np.ndarray] = None,
                     warm_beta: Optional[np.ndarray] = None) -> int:
        """Admit one GROUP BY query as a resident lane BLOCK (phase I).

        The block holds ``G = num_groups`` per-group lanes -- lane g's
        bootstrap key is ``fold_in(key, g)``, its slot table stratum g of
        the pool's shared sample key -- and is ticked as ONE shared-scan
        unit alongside the tiers: one packed gather plus one
        segment-aggregated ESTIMATE per tick, whatever G is.  Admission is
        atomic (no ticket queue: the block's carry is built here) and
        retirement is atomic too -- the response lands in :attr:`results`
        once EVERY group has converged, failed, or exhausted its iteration
        budget, carrying per-group answers and verdicts.

        ``warm_n0 (G,)`` / ``warm_beta (G, 2)`` (both or neither) warm-start
        every lane of the block from a cached grouped entry (phase H x I).
        """
        if (warm_n0 is None) != (warm_beta is None):
            raise ValueError("warm_n0 and warm_beta come together")
        if not self.supports_grouped(query):
            raise ValueError(
                f"lane pool cannot serve grouped func={query.func!r} "
                f"metric={query.metric!r} (needs a moment-family func, "
                f"metric {self._spec['metric']!r}, absolute epsilon, no "
                f"predicate, data_shards == 1)")
        if key is None:
            self.key, key = jax.random.split(self.key)
        G = self.data.num_groups
        scale_row = self._scale_rows.get(query.func)
        if scale_row is None:
            scale_row = estimators.population_scale_row(
                query.func, self.data.scale)
            self._scale_rows[query.func] = scale_row
        fid = self._family[query.func]
        keys = jax.vmap(lambda g: jax.random.fold_in(jnp.asarray(key), g))(
            jnp.arange(G))
        warm = None
        if warm_n0 is not None:
            warm_n0 = jnp.asarray(np.clip(
                np.asarray(warm_n0, np.int64).reshape((G,)),
                1, self._spec["n_cap"]).astype(np.int32)).reshape(G, 1)
            warm_beta = jnp.asarray(
                np.asarray(warm_beta, np.float32).reshape((G, 2)))
            warm = jnp.ones((G,), bool)
            self.warm_spliced += 1
        params = make_group_lane_params(
            self._offsets, jnp.asarray(scale_row, jnp.float32), keys,
            jnp.full((G,), float(query.epsilon), jnp.float32),
            jnp.full((G,), float(query.delta), jnp.float32),
            self._sample_key, jnp.full((G,), fid, jnp.int32),
            n_cap=self._spec["n_cap"], warm=warm, warm_n0=warm_n0,
            warm_beta=warm_beta, slot_idx=self._grouped_tables())
        state = init_lane_state(
            keys, 1, n_cap=self._spec["n_cap"],
            c_dim=self.data.values.shape[1], p_dim=1,
            n_min=self._spec["n_min"], max_iters=self._spec["max_iters"],
            dtype=self.data.values.dtype)
        qid = self._next_qid
        self._next_qid += 1
        self.submitted += 1
        self.grouped_submitted += 1
        if self._block_shapes is None:
            self._block_shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                (state, params))
        self._blocks[qid] = _Block(
            qid=qid, func=query.func, state=state, params=params,
            submitted_s=time.perf_counter(), admitted_tick=self.ticks,
            warm=warm is not None)
        # A grouped block's shared-scan program (seg_cap static) may not
        # have compiled yet; admission is a config event, not steady state.
        self._note_new_program_config()
        return qid

    # -- scheduling ---------------------------------------------------------
    def _place_tier(self) -> Optional[int]:
        """Width-aware placement: the free-laned tier with the smallest
        active watermark -- a fresh lane rides the narrowest bucket any
        free lane can offer."""
        best, best_w = None, None
        for ti, t in enumerate(self._tiers):
            if t.busy == self.tier_lanes:
                continue
            w = t.width
            if best is None or w < best_w:
                best, best_w = ti, w
        return best

    def _refill(self) -> None:
        if not self._queue:
            return
        now = time.perf_counter()
        m = self.data.num_groups
        tl = self.tier_lanes
        if self._slo is not None:
            # Load shedding, sweep half: a queued ticket whose deadline
            # passed while it waited is answered by pilot NOW instead of
            # burning a lane on an already-missed SLO.
            for tk in [t for t in self._queue
                       if t.deadline_at is not None and t.deadline_at <= now]:
                self._queue.remove(tk)
                self._shed(tk, now, blown=True)
        # One padded splice batch per tier that receives lanes this round.
        rounds: Dict[int, list] = {}
        while self._queue:
            ti = self._place_tier()
            if ti is None:
                break
            # SLO-aware admission: highest priority, then WFQ virtual
            # finish time, then earliest deadline, then FIFO (queues are
            # small; linear scan is fine).
            tk = min(self._queue, key=lambda t: t.order)
            self._queue.remove(tk)
            if self._slo is not None and tk.deadline_at is not None:
                # Deadline-driven degradation: if the cost model predicts
                # the full-fidelity run cannot fit the remaining budget,
                # relax epsilon along Eq. 13 to the largest configuration
                # that does; if nothing fits, shed.  The splice below runs
                # the lane AT the delivered bound.
                plan = self._slo.plan(
                    func=tk.func, epsilon=tk.epsilon,
                    deadline_at=tk.deadline_at, now=now,
                    warm_n0=tk.warm_n0, warm_beta=tk.warm_beta)
                if plan.action == "shed":
                    self._shed(tk, now, blown=False)
                    continue
                if plan.action == "degrade":
                    tk.delivered_epsilon = plan.epsilon
                    tk.degraded = True
                    self.degraded += 1
                    if tk.warm_n0 is not None:
                        # Re-aim the warm tick-0 jump at the RELAXED bound
                        # (Eq. 13 forward on the cached coefficients).
                        tk.warm_n0 = np.clip(
                            predict_n0(tk.warm_beta, plan.epsilon,
                                       n_min=self._spec["n_min"]),
                            1, self._spec["n_cap"]).astype(np.int32)
            tier = self._tiers[ti]
            lane = next(i for i, t in enumerate(tier.occupant) if t is None)
            tk.spliced_s, tk.spliced_tick = now, self.ticks
            tk.spliced_width = tier.width
            tier.occupant[lane] = tk
            # The splice resets the lane's watermark on device; mirror it
            # host-side so the lane's RETIRED predecessor's width neither
            # repels the next placement nor inflates ``spliced_width``.
            tier.filled_host[lane] = 0
            if self._wfq is not None:
                self._wfq.on_admit(tk.vft)
            rounds.setdefault(ti, []).append((lane, tk))
        for ti, picks in rounds.items():
            tier = self._tiers[ti]
            # Pad the round to tier width with out-of-range lane indices
            # (dropped by the splice) so every round -- and every tier --
            # hits the one compiled splice program.
            lanes = np.full((tl,), tl, np.int32)
            keys = np.zeros((tl,) + picks[0][1].key.shape,
                            picks[0][1].key.dtype)
            rows = np.ones((tl, m), np.float32)
            eps = np.ones((tl,), np.float32)
            dts = np.full((tl,), 0.05, np.float32)
            fids = np.zeros((tl,), np.int32)
            warm = np.zeros((tl,), bool)
            wn0 = np.zeros((tl, m), np.int32)
            wb = np.zeros((tl, m + 1), np.float32)
            for j, (lane, tk) in enumerate(picks):
                lanes[j], keys[j], rows[j] = lane, tk.key, tk.scale_row
                eps[j], dts[j], fids[j] = tk.eps_run, tk.delta, tk.fid
                if tk.warm_n0 is not None:
                    warm[j], wn0[j], wb[j] = True, tk.warm_n0, tk.warm_beta
                    self.warm_spliced += 1
            tier.state, tier.params = _splice(
                tier.state, tier.params, lanes, keys, rows, eps, dts, fids,
                warm, wn0, wb, n_min=self._spec["n_min"])

    # -- phase J: load shedding ---------------------------------------------
    def _pilot_table(self) -> Array:
        """The shed path's (m, n_pilot) slot tables under the CURRENT
        sample key -- built once per epoch (rotation invalidates), shared
        by every pilot answer in it."""
        if self._pilot_tab is None:
            offs = jnp.asarray(np.asarray(self.data.offsets))
            starts = offs[:-1].astype(jnp.int32)
            sizes = (offs[1:] - offs[:-1]).astype(jnp.int32)
            n_pilot = int(min(self._spec["n_min"], self._spec["n_cap"]))
            self._pilot_tab = counter_slot_table(
                self._sample_key, starts, sizes, n_pilot)
        return self._pilot_tab

    def _shed(self, tk: _Ticket, now: float, *, blown: bool) -> None:
        """Answer ``tk`` immediately from an n_min pilot sample.

        The response carries the MEASURED pilot error as its delivered
        epsilon (the bound the answer actually satisfies) and the reduced
        pilot replicate count -- the delivered-B half of the degradation
        contract.  One pilot B per pool means one compiled pilot program
        per estimator func; an overloaded refill may shed a whole sweep of
        blown tickets, and each must stay a single warm dispatch.  The
        request never occupies a lane.
        """
        del blown
        if self._pilot_values is None:
            # The pilot gathers on the UNSHARDED host values: one tiny
            # (m, n_min) dispatch, layout-independent, so shedding works
            # identically for flat, tiered, and sharded pools.
            self._pilot_values = jnp.asarray(np.asarray(self.data.values))
        pilot_B = max(PILOT_B_FLOOR, int(self._spec["B"]) // 4)
        e, theta = _pilot_estimate(
            self._pilot_values, self._pilot_table(),
            jnp.asarray(self._group_sizes_host.astype(np.int32)),
            jnp.asarray(tk.scale_row, jnp.float32), jnp.asarray(tk.key),
            tk.delta, est_name=tk.func, B=pilot_B,
            metric=self._spec["metric"])
        # One explicit sync for both outputs -- the pilot result is
        # consumed host-side here by design (implicit syncs in the tick
        # path trip the sanitizer's transfer guard).
        err, theta_host = self.recorder.device_get((e, theta))
        err = float(err)
        n_pilot = int(min(self._spec["n_min"], self._spec["n_cap"]))
        n = np.minimum(self._group_sizes_host, n_pilot)
        rows = int(n.sum())
        self.results[tk.qid] = PoolResponse(
            qid=tk.qid, func=tk.func, theta=theta_host,
            error=err, success=bool(err <= tk.epsilon), failed=False,
            n=n, iterations=0, rows_sampled=rows,
            wall_time_s=time.perf_counter() - tk.submitted_s,
            queue_wait_s=now - tk.submitted_s,
            ticks_in_lane=0, lane=-1, tier=-1, spliced_tier_width=0,
            beta=None, warm=False, epsilon=tk.epsilon,
            delivered_epsilon=max(tk.epsilon, err), delivered_B=pilot_B,
            degraded=False, shed=True, tenant=tk.tenant)
        self.shed += 1
        self.retired += 1
        self._retired_rows += rows
        self._shard_rows_retired[0] += rows

    def _harvest(self) -> int:
        """Retire finished lanes; returns the number retired this sync."""
        max_iters = self._spec["max_iters"]
        now = time.perf_counter()
        n_retired = 0
        for ti, tier in enumerate(self._tiers):
            if tier.busy == 0:
                continue
            s = tier.state
            done, failed, k, filled = self.recorder.device_get(
                (s.done, s.failed, s.k, s.filled))
            tier.filled_host = np.asarray(filled, np.int64)
            finished = [lane for lane, t in enumerate(tier.occupant)
                        if t is not None
                        and (done[lane] or failed[lane]
                             or k[lane] >= max_iters)]
            if not finished:
                continue
            e, n_cur, iters, theta, beta = self.recorder.device_get(
                (s.e, s.n_cur, s.iters, s.theta, s.beta))
            for lane in finished:
                t = tier.occupant[lane]
                rows = int(filled[lane].sum())
                self.results[t.qid] = PoolResponse(
                    qid=t.qid, func=t.func, theta=np.asarray(theta[lane]),
                    error=float(e[lane]), success=bool(done[lane]),
                    failed=bool(failed[lane]), n=np.asarray(n_cur[lane]),
                    iterations=int(iters[lane]), rows_sampled=rows,
                    wall_time_s=now - t.submitted_s,
                    queue_wait_s=t.spliced_s - t.submitted_s,
                    ticks_in_lane=self.ticks - t.spliced_tick,
                    lane=ti * self.tier_lanes + lane, tier=ti,
                    spliced_tier_width=t.spliced_width,
                    beta=np.asarray(beta[lane]),
                    warm=t.warm_n0 is not None, epsilon=t.epsilon,
                    delivered_epsilon=t.eps_run,
                    delivered_B=int(self._spec["B"]),
                    degraded=t.degraded, migrations=t.migrations,
                    tenant=t.tenant)
                if self._slo is not None:
                    # Teach the cost model: the bound the lane ran at, how
                    # wide it grew, how long it stayed resident.
                    self._slo.cost.observe_retirement(
                        t.func, t.eps_run, int(filled[lane].max()),
                        self.ticks - t.spliced_tick)
                tier.occupant[lane] = None
                self.retired += 1
                self._retired_rows += rows
                if self._layout is not None:
                    self._shard_rows_retired += self._layout.shard_rows(
                        filled[lane])
                else:
                    self._shard_rows_retired[0] += rows
                n_retired += 1
        return n_retired

    def _harvest_blocks(self) -> int:
        """Retire grouped blocks whose EVERY lane has finished (converged,
        failed, or out of iterations) -- atomic retirement: per-group
        answers leave together, as one :class:`GroupPoolResponse`."""
        if not self._blocks:
            return 0
        max_iters = self._spec["max_iters"]
        now = time.perf_counter()
        finished: List[int] = []
        for qid, blk in self._blocks.items():
            s = blk.state
            done, failed, k = self.recorder.device_get(
                (s.done, s.failed, s.k))
            if not bool(np.all(done | failed | (k >= max_iters))):
                continue
            e, n_cur, iters, theta, beta, filled = self.recorder.device_get(
                (s.e, s.n_cur, s.iters, s.theta, s.beta, s.filled))
            rows = int(np.asarray(filled).sum())
            self.results[qid] = GroupPoolResponse(
                qid=qid, func=blk.func,
                theta=np.asarray(theta)[:, 0, 0],
                error=np.asarray(e), group_success=np.asarray(done),
                success=bool(np.all(done)), failed=bool(np.any(failed)),
                n=np.asarray(n_cur)[:, 0],
                iterations=np.asarray(iters),
                rows_sampled=rows, wall_time_s=now - blk.submitted_s,
                queue_wait_s=0.0,
                ticks_in_block=self.ticks - blk.admitted_tick,
                beta=np.asarray(beta), warm=blk.warm)
            self.retired += 1
            self.grouped_retired += 1
            self._retired_rows += rows
            self._shard_rows_retired[0] += rows
            finished.append(qid)
        for qid in finished:
            del self._blocks[qid]
        return len(finished)

    def _maybe_migrate(self) -> None:
        """Cross-tier lane migration (phase J): when ONE straggler's
        watermark drives a tier's ESTIMATE bucket above what its
        tier-mates need, splice it into a tier already riding that bucket
        (or an empty one) at this sync point.  The move is a full row copy
        of the lane's carry (:func:`_migrate`), so the trajectory is
        bit-equal to staying put -- migration changes what the lane's OLD
        neighbors pay, never any answer.  At most one move per sync: the
        watermark view refreshes per harvest anyway."""
        if not self.migrate_enabled:
            return
        for si, src in enumerate(self._tiers):
            occ = [(int(src.filled_host[i].max()), i)
                   for i, tk in enumerate(src.occupant) if tk is not None]
            if len(occ) < 2:
                continue
            occ.sort(reverse=True)
            (w1, lane1), (w2, _) = occ[0], occ[1]
            if self.bucket_of(w1) <= self.bucket_of(w2):
                continue   # the straggler isn't (alone) driving the bucket
            for di, dst in enumerate(self._tiers):
                if di == si or dst.busy == self.tier_lanes:
                    continue
                if dst.busy and self.bucket_of(dst.width) \
                        < self.bucket_of(w1):
                    continue   # would widen the destination's bucket
                dst_lane = next(i for i, t in enumerate(dst.occupant)
                                if t is None)
                src.state, dst.state, dst.params = _migrate(
                    src.state, src.params, dst.state, dst.params,
                    lane1, dst_lane)
                tk = src.occupant[lane1]
                src.occupant[lane1] = None
                dst.occupant[dst_lane] = tk
                dst.filled_host[dst_lane] = src.filled_host[lane1]
                src.filled_host[lane1] = 0
                tk.migrations += 1
                self.migrations += 1
                return

    @property
    def values(self) -> "tuple | Array":
        """The resident table, placed once in ``__init__`` (phase
        ``table_layout``): :attr:`columns` on one device; the ``(N_pad,
        c)`` table padded to the shard layout (and row-sharded over the
        mesh) when ``data_shards > 1``."""
        return self._values

    @property
    def columns(self) -> Tuple[Array, ...]:
        """The table as the tiers and blocks read it, built once in
        ``__init__`` (phase ``table_layout``): a tuple of ``c`` 1-D columns
        (:func:`~repro.core.fused.as_columns`), each row-sharded over the
        mesh like :attr:`values` when the pool has one."""
        return self._cols

    @property
    def ticks_per_sync(self) -> int:
        return self._ticks_per_sync

    @ticks_per_sync.setter
    def ticks_per_sync(self, value: int) -> None:
        value = int(value)
        if getattr(self, "_ticks_per_sync", None) != value:
            self._ticks_per_sync = value
            # num_ticks is static: a retuned cadence compiles one new
            # program, legitimately.
            self._note_new_program_config()

    def _note_new_program_config(self) -> None:
        """A new static/shape configuration is about to compile; re-arm the
        steady-state sentinel so the expected miss isn't counted."""
        self._steady_cache0 = None

    def _program_cache_size(self) -> int:
        size = fused_step._cache_size()
        if self._mesh is not None:
            size += sharded_step_cache_size()
        return int(size)

    def _tier_program(self, tier: _Tier):
        """``(program, args, kwargs)`` of one tier dispatch."""
        if self._mesh is not None:
            step = self._step_cache.get(self.ticks_per_sync)
            if step is None:
                step = make_sharded_step(
                    self._mesh, num_ticks=self.ticks_per_sync, **self._spec)
                self._step_cache[self.ticks_per_sync] = step
            return (step, (self._cols, tier.state, tier.params,
                           self._shard_spec), {})
        args = (self._cols, self._offsets, tier.state, tier.params)
        if self._layout is not None:
            # Single-device run of the SAME shard layout (mesh=False): the
            # sequential segment fold the mesh psum reproduces.  seg_window
            # passes through exactly as compiled for the mesh spec -- no
            # ext_cap re-resolution in between.
            args += (self._shard_spec,)
        return fused_step, args, dict(num_ticks=self.ticks_per_sync,
                                      **self._spec)

    def _block_program(self, state: LaneState, params: LaneParams):
        """``(program, args, kwargs)`` of one grouped block dispatch."""
        return fused_step, (self._cols, self._goffsets, state, params), \
            dict(num_ticks=self.ticks_per_sync, seg_cap=self._gseg_cap,
                 **self._spec)

    def lowered_tick(self, grouped: bool = False) -> "jax.stages.Lowered":
        """The program a tier dispatch runs (``grouped``: a grouped block's
        dispatch, once the pool has admitted one), lowered at the pool's
        live shapes and cadence -- for inspecting what a tick compiles to
        (``.as_text()``, ``.compile().memory_analysis()``)."""
        if not grouped:
            step, args, kw = self._tier_program(self._tiers[0])
        elif self._block_shapes is None:
            raise ValueError("the pool has admitted no grouped block yet")
        else:
            step, args, kw = self._block_program(*self._block_shapes)
        return step.lower(*args, **kw)

    def tick(self) -> int:
        """One scheduling round: refill, run ``ticks_per_sync`` loop ticks
        per busy tier (one dispatch each) plus one shared-scan dispatch per
        resident grouped block, harvest, maybe migrate a straggler lane.
        Returns busy lanes + blocks.

        The round runs under :func:`sanitize.guarded` (inert unless
        MISS_SANITIZE is set): every device->host sync in the pump path
        must be an explicit ``jax.device_get`` harvest.  Afterwards the
        recompile sentinel attributes any program-cache growth not
        explained by a config event to ``steady_recompiles``.

        Spans (``self.recorder``): ``tick``, with ``refill`` (queue pick,
        padded batch, splice dispatch), ``dispatch`` (the asynchronous
        step enqueues) and ``harvest`` (retirement and migration) inside.
        """
        with self.recorder.phase("tick"):
            with sanitize.guarded():
                out = self._tick_inner()
            size = self._program_cache_size()
            if self._steady_cache0 is None:
                self._steady_cache0 = size
            elif size > self._steady_cache0:
                self.steady_recompiles += size - self._steady_cache0
                self._steady_cache0 = size
            return out

    def _tick_inner(self) -> int:
        rec = self.recorder
        t0 = time.perf_counter()
        self._maybe_rotate()
        with rec.phase("refill"):
            self._refill()
        ran = False
        round_rung = 0
        with rec.phase("dispatch"):
            for ti, tier in enumerate(self._tiers):
                busy = tier.busy
                if not busy:
                    continue
                if ti not in self._warmed_tiers:
                    # This tier's first dispatch compiles its width's
                    # program.
                    self._warmed_tiers.add(ti)
                    self._note_new_program_config()
                round_rung = max(round_rung, tier.width)
                step, args, kw = self._tier_program(tier)
                tier.state = step(*args, **kw)
                self.dispatches += 1
                self.psum_bytes += self._psum_tick_bytes * self.ticks_per_sync
                self.lane_ticks_busy += busy * self.ticks_per_sync
                self._active_frac_sum += busy / self.tier_lanes
                ran = True
            # Phase I: grouped blocks ride the same scheduling round -- one
            # shared-scan dispatch per block, however many groups it holds.
            for blk in self._blocks.values():
                step, args, kw = self._block_program(blk.state, blk.params)
                blk.state = step(*args, **kw)
                self.dispatches += 1
                self.block_ticks += self.ticks_per_sync
                ran = True
        if not ran:
            return 0
        self.ticks += self.ticks_per_sync
        with rec.phase("harvest"):
            self._harvest()
            self._harvest_blocks()
            if self._slo is not None:
                # Teach the cost model what a scheduling round costs at
                # this compute rung (the harvest's device_get closed the
                # round, so the wall time covers dispatch + sync).
                self._slo.cost.observe_round(
                    time.perf_counter() - t0, self.ticks_per_sync,
                    round_rung)
            self._maybe_migrate()
        return self.busy_lanes + self.busy_blocks

    def drain(self, max_ticks: int = 100_000) -> List[PoolResponse]:
        """Tick until the queue and every lane are empty; pop and return
        every retired result not yet collected, in qid order.

        Popping is what keeps an unbounded query stream at bounded memory:
        ``results`` is a hand-off buffer between harvest and the caller,
        not a history."""
        guard = 0
        while (self._queue or self.busy_lanes or self._blocks) \
                and guard < max_ticks:
            self.tick()
            guard += self.ticks_per_sync
        return [self.results.pop(qid) for qid in sorted(self.results)]

    # -- epoch policy -------------------------------------------------------
    def set_sample_key(self, sample_key: Array) -> None:
        """Rotate the pool-shared slot->row binding (reshuffle epoch).

        Only legal while the pool is idle: a resident lane's filled prefix
        is defined by the OLD binding, so rotating under it would break the
        nesting invariant.  For a live session that cannot guarantee
        idleness, use :meth:`request_sample_key` instead.
        """
        if self.busy_lanes or self._queue or self._blocks:
            raise RuntimeError("cannot rotate sample_key with queries in "
                               "flight; drain() first or use "
                               "request_sample_key()")
        self._apply_sample_key(sample_key)

    def request_sample_key(self, sample_key: Array) -> bool:
        """Deferred epoch rotation for a LIVE pool: apply the new binding
        now if no lane is busy, else park it and apply at the next idle
        point (the start of the first tick with every lane free -- resident
        prefixes are what the binding defines, so a rotation between
        harvest and splice is exact; still-QUEUED tickets simply splice
        under the new key).  Returns True when applied immediately.

        A newer request supersedes an unapplied one -- the pool only ever
        jumps to the latest epoch.
        """
        self._pending_sample_key = jnp.asarray(sample_key)
        return self._maybe_rotate()

    def _maybe_rotate(self) -> bool:
        if self._pending_sample_key is None or self.busy_lanes \
                or self._blocks:
            return False
        key, self._pending_sample_key = self._pending_sample_key, None
        self._apply_sample_key(key)
        return True

    def _apply_sample_key(self, sample_key: Array) -> None:
        self._sample_key = jnp.asarray(sample_key)
        if self._layout is not None:
            from ..core.sampling import sharded_slot_tables
            slot_idx = sharded_slot_tables(
                self._sample_key, self._layout,
                local_rows=self._mesh is not None)
            if self._mesh is not None:
                slot_idx = core_mesh.put_sharded(self._mesh, slot_idx)
        else:
            starts = self._offsets[:-1].astype(jnp.int32)
            sizes = (self._offsets[1:] - self._offsets[:-1]).astype(jnp.int32)
            slot_idx = counter_slot_table(
                self._sample_key, starts, sizes, self._spec["n_cap"])
        for tier in self._tiers:
            tier.params = tier.params._replace(slot_idx=slot_idx)
        # Grouped blocks and shed pilots build their tables from the pool
        # key; rotation (idle-only: no blocks resident here) just
        # invalidates the per-epoch caches.
        self._gtables = None
        self._pilot_tab = None
        self.sample_epochs += 1

    # -- accounting ---------------------------------------------------------
    def tier_watermarks(self) -> List[int]:
        """Per-tier max active watermark (host view, lags one sync)."""
        return [t.width for t in self._tiers]

    def bucket_of(self, watermark: int) -> int:
        """The ESTIMATE bucket width a lane with ``watermark`` filled rows
        rides at (the step's static ladder) -- what admission minimizes.

        A sharded pool's buckets cover SEGMENT fills, so the global
        watermark is first translated through the layout's worst-case
        per-shard share (a placement cost model only -- tiering changes
        cost, never answers)."""
        n_cap, n_max = self._spec["n_cap"], self._spec["n_max"]
        if self._layout is not None:
            seg_cap = self._layout.seg_cap
            widths = bucket_ladder(seg_cap, min(n_max, seg_cap))
            watermark = int(np.ceil(
                watermark * self._layout.max_shard_frac()))
        else:
            widths = bucket_ladder(n_cap, n_max)
        for w in widths:
            if watermark <= w:
                return w
        return widths[-1]

    def shard_dispatch_rows(self) -> np.ndarray:
        """(S,) per-shard slot residency: retired queries' shares plus the
        currently-resident lanes' watermarks pushed through the layout's
        ownership tables -- how the pool's gather/bootstrap work actually
        split across devices (phase G accounting)."""
        out = self._shard_rows_retired.copy()
        for t in self._tiers:
            for i, tk in enumerate(t.occupant):
                if tk is None:
                    continue
                if self._layout is not None:
                    out += self._layout.shard_rows(t.filled_host[i])
                else:
                    out[0] += int(t.filled_host[i].sum())
        return out

    def stats(self) -> Dict[str, float]:
        cap = max(self.ticks * self.lanes, 1)
        resident = sum(
            int(t.filled_host[i].sum())
            for t in self._tiers
            for i, tk in enumerate(t.occupant) if tk is not None)
        rows_gathered = self._retired_rows + resident
        return {
            "lanes": self.lanes,
            "tiers": self.tiers,
            "data_shards": self.data_shards,
            "shard_rows": [int(x) for x in self.shard_dispatch_rows()],
            "ticks_per_sync": self.ticks_per_sync,
            "ticks": self.ticks,
            "dispatches": self.dispatches,
            "submitted": self.submitted,
            "retired": self.retired,
            "grouped_submitted": self.grouped_submitted,
            "grouped_retired": self.grouped_retired,
            "busy_blocks": self.busy_blocks,
            "block_ticks": self.block_ticks,
            "queue_depth": self.queue_depth,
            "peak_queue_depth": self.peak_queue_depth,
            "lane_occupancy": self.lane_ticks_busy / cap,
            # Phase-E observables: what fraction of a dispatch's lanes were
            # live (the gating's compute bound), and how many rows the
            # gated window gathers actually pulled per scheduling round.
            "active_lane_fraction": (
                self._active_frac_sum / max(self.dispatches, 1)),
            "rows_gathered": float(rows_gathered),
            "rows_per_tick": rows_gathered / max(self.ticks, 1),
            "sample_epochs": self.sample_epochs,
            "pending_rotation": self._pending_sample_key is not None,
            "warm_spliced": self.warm_spliced,
            # Phase-J overload counters (0 with the policies off).
            "shed": self.shed,
            "degraded": self.degraded,
            "migrations": self.migrations,
            # Recompile sentinel: programs compiled mid-steady-state (no
            # retune / warmup event to explain them).  Anything nonzero is
            # the PR 9 `_unstack` bug class; tests assert it stays 0.
            "steady_recompiles": self.steady_recompiles,
            # The process-wide make_sharded_step memo LRU (bounded; every
            # pool shares it, so this is global occupancy, not per-pool).
            "sharded_step_cache": sharded_step_cache_size(),
            # Where the table sits: resident bytes by device id, and the
            # bytes the pool sent from the host to place it.
            "table_bytes_by_device": dict(self.table_bytes_by_device),
            "table_host_bytes": self.table_host_bytes,
            "psum_bytes": self.psum_bytes,
        }
