"""Fused on-device L2Miss: the whole MISS loop as one XLA program.

Beyond-paper optimization (DESIGN.md SS7 phases B + C): the host-loop
Algorithm 3 round-trips device<->host every iteration (sample sizes out,
errors in).  On a real TPU pod each round-trip costs dispatch latency and
loses the collective schedule; here the *entire* sample->estimate->fit->
predict->test loop runs inside ``lax.while_loop`` with fixed-capacity
buffers:

  * sample buffer   (q, m, n_cap, c) -- CARRIED across iterations.  Slot j of
    group i is bound to a fixed uniform row index by a counter PRNG
    (sampling.counter_slot_table), so the sample sequence is *nested*:
    iteration k+1's sample extends iteration k's prefix instead of replacing
    it.  Each iteration reads an (m, ext_cap) extension window past the
    filled watermark -- per-iteration gather drops from O(n_cap) to
    O(ext_cap) -- and the distinct rows gathered over a run equal the final
    watermark sum(filled) (reported as rows_sampled; see DESIGN.md SS3.2).
    The window gather is predicated per lane (phase E): frozen/parked lanes
    skip it via a real ``lax.cond`` branch, bounding a tick's gather
    traffic by its ACTIVE lanes.
  * width-adaptive ESTIMATE (phase C): the bootstrap runs on a power-of-two
    width bucket of the carried buffer covering the current watermark, not
    on the full ``n_cap`` capacity -- ``lax.switch`` over a static bucket
    ladder.  Replicate weights come from the counter PRNG (entry (j, b) =
    poisson1(hash3(seed, j, b)), j the absolute slot), so the draws are
    invariant to the bucket width.  With ``use_kernel`` the moment
    estimators route through ``kernels/poisson_bootstrap`` and the weights
    are generated in VMEM, never materialized in HBM.
  * error profile   (max_iters, m) + (max_iters,) -- row-masked WLS
  * two-point init rows are drawn inside the loop from the lane's iteration
    counter

``sample_key`` (optional, defaults to ``key``) seeds the slot->row binding
separately from the bootstrap stream, so a server can share one permuted
prefix across many queries (serve/session.py) while keeping bootstrap
replicates independent.

Resumable step architecture (phase D): the loop state is the explicit
:class:`LaneState` carry and one iteration is the standalone jitted
:func:`fused_step` -- SAMPLE -> ESTIMATE -> FIT -> PREDICT -> TEST for all
``q`` lanes, predicated per lane.  :func:`fused_l2miss_lanes` is now a thin
``lax.while_loop`` wrapper over the very same step body, so closed-loop and
host-ticked trajectories are identical by construction.  Crucially the tick
counter ``k`` is PER LANE: in the closed loop every lane starts at k=0 and
the counters advance in lockstep (bit-identical to the old scalar counter),
while a host ticker (serve/lane_pool.py) can retire a converged lane and
splice a fresh query into it mid-flight -- the spliced lane restarts at its
own k=0 with its own counter-PRNG streams, so its trajectory is the one a
solo run with the same (key, sample_key) would produce.

Per-lane estimators: with ``est_name=None`` each lane selects its estimator
by moment-family index (``LaneParams.est_fids``) routed through
``lax.switch`` inside ESTIMATE (core/bootstrap.estimate_error_lanes_het) --
mean/sum/count/std/var/proportion queries share one resident program
instead of one dispatch per func group.

Entry points: the lane pool (serve/lane_pool.py) ticks :func:`fused_step`
and the sharded step; :func:`fused_l2miss_lanes` and :func:`fused_l2miss`
run the same bodies as closed loops, the solo references the tests hold
the pool to; :func:`fused_grouped` is the closed loop of one GROUP BY
block, which the host engine runs for grouped requests the pool cannot
take.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bootstrap, error_model, sampling
from .estimators import get as get_estimator
from .estimators import moment_family_index
from ..kernels import prng

Array = jax.Array
LOG_FLOOR = -60.0

# Domain-separation constants for the counter-PRNG streams.
_SALT_SAMPLE = sampling.SLOT_SALT   # slot -> row binding (sampling.py owns it)
_SALT_BOOT = 0xB007        # per-lane bootstrap seed base
_SALT_GROUP = 0x7F4A7C15   # per-(iteration, group) bootstrap stream split
_SALT_SHARD = sampling.SHARD_SALT   # per-shard bootstrap stream split


class FusedResult(NamedTuple):
    n: Array            # (m,) final sizes
    error: Array        # final estimated error
    theta: Array        # (m, p) final estimate (scaled)
    iterations: Array   # iterations executed
    success: Array      # bool: constraint met
    failed: Array       # bool: Algorithm-2 unrecoverable failure
    beta: Array         # (m+1,) final model parameters
    r2: Array
    profile_n: Array    # (max_iters, m)
    profile_e: Array    # (max_iters,)
    rows_sampled: Array # total rows gathered (== sum of the filled
                        #   watermark).  Only ACTIVE ticks gather (the
                        #   per-lane gated window; frozen/parked lanes skip
                        #   their gather entirely), so this also equals the
                        #   rows the lane's active iterations pulled from HBM.


class LaneState(NamedTuple):
    """The carried state of the fused loop -- one row per query lane.

    This is the resume point: ``fused_step`` maps ``LaneState -> LaneState``
    and everything a lane's future depends on is in its rows here plus its
    rows of :class:`LaneParams`.  A host ticker persists it between steps;
    the closed loop threads it through ``lax.while_loop``.
    """
    keys: Array         # (q, 2) fallback-backend bootstrap keys
    k: Array            # (q,) per-lane tick counter (lockstep in the
                        #   closed loop; restarts at 0 on a pool refill)
    iters: Array        # (q,) per-lane active-iteration count
    n_cur: Array        # (q, m)
    filled: Array       # (q, m) gathered-slot watermark (monotone)
    buf: Array          # (q, m, n_cap, c) carried nested samples
    prof_n: Array       # (q, max_iters, m)
    prof_loge: Array    # (q, max_iters)
    e: Array            # (q,)
    theta: Array        # (q, m, p)
    done: Array         # (q,) sticky
    failed: Array       # (q,) sticky
    beta: Array         # (q, m + 1)
    r2: Array           # (q,)


class LaneParams(NamedTuple):
    """Per-lane query parameters -- constant across ticks, spliceable per lane.

    Splitting these out of :class:`LaneState` is what makes retire-and-
    refill cheap: a pool swaps ONE lane's rows here (plus resetting its
    state rows) without touching the neighbors or recompiling anything.
    ``slot_idx`` is the counter-PRNG slot->row binding -- shape ``(m,
    n_cap)`` when all lanes share one sample key (the server epoch policy)
    or ``(q, m, n_cap)`` for per-lane bindings.

    Warm start (DESIGN.md SS7 phase H): a lane with ``warm[i]`` set skips
    the two-point init design entirely -- its first tick jumps straight to
    the cached prediction ``warm_n0[i]`` and its FIT carry is seeded with
    the prior coefficients ``warm_beta[i]``.  The normal TEST/extend logic
    is the verification: if the one-tick ESTIMATE confirms the bound the
    lane retires in a single sync; a stale prediction refines via the
    cached-coefficient local model until the lane has accumulated its own
    ``l``-deep profile, after which the ordinary WLS fit takes over.  Cold
    lanes carry all-False / zero rows here and behave exactly as before.
    """
    scale: Array        # (q, m) per-group |D|_i scale (1.0 for consistent f)
    epsilons: Array     # (q,)
    deltas: Array       # (q,)
    est_fids: Array     # (q,) int32 moment-family indices (est_name=None)
    boot_base: Array    # (q,) uint32 per-lane bootstrap seed base
    slot_idx: Array     # (m, n_cap) shared | (q, m, n_cap) per lane
    warm: Array         # (q,) bool: lane starts from a cached prediction
    warm_n0: Array      # (q, m) int32 predicted n* (the tick-0 jump target)
    warm_beta: Array    # (q, m+1) f32 cached error-model coefficients
    group_sizes: Array  # (q, m) int32 rows available to each lane's groups.
                        #   Ordinary pools broadcast the shared layout's
                        #   sizes; a grouped lane BLOCK (phase I) binds lane
                        #   g to group g, so its row is that one group's
                        #   size -- the per-lane sample-size ceiling.


def _bucket_widths(n_cap: int, base: int) -> Tuple[int, ...]:
    """Static power-of-two width ladder base, 2*base, ... topped by n_cap."""
    base = min(max(int(base), 1), n_cap)
    widths = []
    w = base
    while w < n_cap:
        widths.append(w)
        w *= 2
    widths.append(n_cap)
    return tuple(widths)


def _window_ladder(cap: int, base: int) -> Tuple[int, ...]:
    """Doubling ladder with midpoints (base, 1.5b, 2b, 3b, 4b, ...) to cap.

    The sharded step's per-lane window rungs: midpoints cap the padding
    waste at 50% where a pure doubling ladder allows 100%, at the cost of
    roughly twice the compiled switch branches.
    """
    base = min(max(int(base), 1), cap)
    rungs = set()
    w = base
    while w < cap:
        rungs.add(w)
        mid = w + w // 2
        if mid < cap:
            rungs.add(mid)
        w *= 2
    rungs.add(cap)
    return tuple(sorted(rungs))


def bucket_ladder(n_cap: int, n_max: int) -> Tuple[int, ...]:
    """The static ESTIMATE width ladder the fused step compiles (phase C).

    Shared with the pool's admission cost model (serve/lane_pool.py), so
    the bucket a scheduler reasons about is the bucket the step executes.
    """
    return _bucket_widths(n_cap, sampling.bucket_cap(min(n_max, n_cap)))


def seg_ladder(seg_cap: int, n_max: int) -> Tuple[int, ...]:
    """Static packed-stream width ladder of the grouped-block ESTIMATE.

    The phase-I analogue of :func:`bucket_ladder`: a grouped block's tick
    scans ONE packed stream of all active lanes' windows, padded up to the
    smallest rung covering the union watermark.  Exposed so the pool's cost
    model and the tests' rows-scanned accounting price exactly the rung
    the compiled step executes.
    """
    return _window_ladder(seg_cap, min(sampling.bucket_cap(n_max), seg_cap))


def grouped_seg_cap(offsets, n_cap: int) -> int:
    """Host-side packed-stream capacity of a grouped block: sum of the
    per-group slot ceilings ``min(size_g, n_cap)`` -- the most slots the
    block's union watermark can ever cover, and therefore the top rung of
    :func:`seg_ladder`."""
    off = np.asarray(offsets)
    sizes = off[1:] - off[:-1]
    return int(np.minimum(sizes, n_cap).sum())


def resolve_ext_cap(n_cap: int, n_max: int, ext_cap: Optional[int] = None) -> int:
    """Extension window: the most new rows one ACTIVE lane-tick may gather.

    Must cover the init levels (or the two-point design would collapse);
    beyond that it trades per-iteration gather width against extra
    refinement iterations when PREDICT wants a bigger jump than the window
    allows.  The window gather is gated per lane (``gate_gather``, a real
    ``lax.cond`` branch): frozen/parked lanes skip theirs, so one tick's
    gather traffic is bounded by ``sum(active) * ext_cap``, not
    ``q * ext_cap``.  Step callers must resolve once and pass the same
    value every tick -- the window size is part of the compiled step
    signature.
    """
    if ext_cap is None:
        ext_cap = min(n_cap, max(sampling.bucket_cap(n_max), n_cap // 8))
    return min(max(ext_cap, n_max), n_cap)


def lane_boot_seed(key: Array) -> Array:
    """uint32 bootstrap seed base for one lane key (the _SALT_BOOT stream).

    Split out so a lane pool splicing a fresh query into lane i derives the
    identical seed a full ``make_lane_params`` rebuild would -- the refilled
    lane's bootstrap stream is the one a solo run with ``key`` would use.
    """
    return jax.random.bits(jax.random.fold_in(key, _SALT_BOOT), (),
                           jnp.uint32)


def resolve_warm_rows(
    q: int,
    m: int,
    warm: Optional[Array] = None,
    warm_n0: Optional[Array] = None,
    warm_beta: Optional[Array] = None,
) -> Tuple[Array, Array, Array]:
    """Concrete warm-start leaves for :class:`LaneParams` (cold when unset).

    ``warm=None`` infers the mask: all-True when a prediction was supplied,
    all-False otherwise.  The leaves are always materialized (never None)
    so cold and warm pools share one pytree structure -- and therefore one
    compiled step/splice program.
    """
    if warm is None:
        warm = jnp.full((q,), warm_n0 is not None, bool)
    else:
        warm = jnp.asarray(warm, bool)
    warm_n0 = (jnp.zeros((q, m), jnp.int32) if warm_n0 is None
               else jnp.asarray(warm_n0, jnp.int32))
    warm_beta = (jnp.zeros((q, m + 1), jnp.float32) if warm_beta is None
                 else jnp.asarray(warm_beta, jnp.float32))
    return warm, warm_n0, warm_beta


def make_lane_params(
    offsets: Array,
    scale: Array,
    keys: Array,
    epsilons: Array,
    deltas: Array,
    sample_keys: Optional[Array] = None,
    est_fids: Optional[Array] = None,
    *,
    n_cap: int,
    warm: Optional[Array] = None,
    warm_n0: Optional[Array] = None,
    warm_beta: Optional[Array] = None,
) -> LaneParams:
    """Build the per-lane query parameters (slot tables + seed bases).

    ``sample_keys``: ``None`` derives one slot->row binding per lane from
    ``keys``; shape ``(2,)`` shares ONE binding (and slot table) across all
    lanes -- the server's shared-prefix epoch policy; shape ``(q, 2)`` pins
    one per lane.  ``warm``/``warm_n0``/``warm_beta`` seed warm-started
    lanes (:func:`resolve_warm_rows`); omitted = every lane cold.
    """
    starts = offsets[:-1].astype(jnp.int32)
    sizes = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    q = epsilons.shape[0]
    skeys = keys if sample_keys is None else sample_keys
    if skeys.ndim == 1:
        slot_idx = sampling.counter_slot_table(skeys, starts, sizes, n_cap)
    else:
        slot_idx = jax.vmap(
            lambda sk: sampling.counter_slot_table(sk, starts, sizes, n_cap)
        )(skeys)
    # Per-lane bootstrap seed base: the per-iteration, per-group streams are
    # counter-derived (hash3) so the loop carries no RNG key state for the
    # default backend.  The non-poisson fallbacks still consume LaneState.keys.
    boot_base = jax.vmap(lane_boot_seed)(keys)                 # (q,)
    if est_fids is None:
        est_fids = jnp.zeros((q,), jnp.int32)
    w, wn0, wb = resolve_warm_rows(q, sizes.shape[0], warm, warm_n0, warm_beta)
    return LaneParams(
        scale=jnp.asarray(scale), epsilons=jnp.asarray(epsilons, jnp.float32),
        deltas=jnp.asarray(deltas, jnp.float32),
        est_fids=jnp.asarray(est_fids, jnp.int32), boot_base=boot_base,
        slot_idx=slot_idx, warm=w, warm_n0=wn0, warm_beta=wb,
        group_sizes=jnp.broadcast_to(sizes[None, :], (q, sizes.shape[0])))


def make_group_lane_params(
    offsets: Array,
    scale: Array,        # (G,) per-group scale (population_scale_row)
    keys: Array,         # (G, 2) per-lane bootstrap keys
    epsilons: Array,     # (G,)
    deltas: Array,       # (G,)
    sample_key: Array,   # (2,) the block's shared stratified-store key
    est_fids: Optional[Array] = None,
    *,
    n_cap: int,
    warm: Optional[Array] = None,
    warm_n0: Optional[Array] = None,     # (G, 1)
    warm_beta: Optional[Array] = None,   # (G, 2)
    slot_idx: Optional[Array] = None,    # prebuilt (G, 1, n_cap) tables
) -> LaneParams:
    """Lane-BLOCK parameters for a grouped query (phase I): lane g <- group g.

    The block runs ``q = G`` lanes of ``m = 1``.  Lane g's slot table is
    the stratified store's stratum table (:func:`~.sampling.
    stratified_slot_tables`) -- identical to the solo table a run on group
    g's slice with ``sample_key = stratum_key(sample_key, g)`` would build,
    shifted to global rows -- and its ``group_sizes`` row is that one
    group's size, so the per-lane clamp in the step body enforces each
    group's own ceiling.  Everything else (bootstrap seed bases, warm rows)
    is derived exactly as :func:`make_lane_params` does, which is what
    makes block trajectories comparable to G solo runs.

    ``slot_idx`` optionally supplies the stratified tables prebuilt (they
    depend only on ``(sample_key, offsets, n_cap)``, so a pool admitting
    many blocks per sample epoch builds them once and passes them in).
    """
    sizes = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    q = epsilons.shape[0]
    if q != sizes.shape[0]:
        raise ValueError(
            f"grouped block wants one lane per group: got {q} lanes for "
            f"{sizes.shape[0]} groups")
    if sample_key.ndim != 1:
        raise ValueError("a grouped block shares one (2,) sample key")
    if slot_idx is None:
        slot_idx = sampling.stratified_slot_tables(sample_key, offsets, n_cap)
    boot_base = jax.vmap(lane_boot_seed)(keys)
    if est_fids is None:
        est_fids = jnp.zeros((q,), jnp.int32)
    w, wn0, wb = resolve_warm_rows(q, 1, warm, warm_n0, warm_beta)
    return LaneParams(
        scale=jnp.asarray(scale, jnp.float32).reshape(q, 1),
        epsilons=jnp.asarray(epsilons, jnp.float32),
        deltas=jnp.asarray(deltas, jnp.float32),
        est_fids=jnp.asarray(est_fids, jnp.int32), boot_base=boot_base,
        slot_idx=slot_idx, warm=w, warm_n0=wn0, warm_beta=wb,
        group_sizes=sizes.reshape(q, 1))


def init_lane_state(
    keys: Array,
    m: int,
    *,
    n_cap: int,
    c_dim: int,
    p_dim: int,
    n_min: int,
    max_iters: int,
    dtype=jnp.float32,
) -> LaneState:
    """Fresh carry for ``q = keys.shape[0]`` lanes (every lane at tick 0)."""
    q = keys.shape[0]
    return LaneState(
        keys=keys,
        k=jnp.zeros((q,), jnp.int32),
        iters=jnp.zeros((q,), jnp.int32),
        n_cur=jnp.full((q, m), n_min, jnp.int32),
        filled=jnp.zeros((q, m), jnp.int32),
        buf=jnp.zeros((q, m, n_cap, c_dim), dtype),
        prof_n=jnp.ones((q, max_iters, m), jnp.float32),
        prof_loge=jnp.zeros((q, max_iters), jnp.float32),
        e=jnp.full((q,), jnp.inf, jnp.float32),
        theta=jnp.zeros((q, m, p_dim), jnp.float32),
        done=jnp.zeros((q,), bool),
        failed=jnp.zeros((q,), bool),
        beta=jnp.zeros((q, m + 1), jnp.float32),
        r2=jnp.zeros((q,), jnp.float32),
    )


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, a
    fresh scope per call (one shared ``named_scope`` object keeps the scope
    it replaced on itself, which reentrant or threaded tracing would mix
    up).  The name reaches the compiled ops' ``op_name`` metadata only."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def as_columns(values: Array, sharding=None) -> Tuple[Array, ...]:
    """The table as every step reads it: ``c`` 1-D columns.

    A row gather from a 2-D ``(N, c)`` table makes XLA relayout the whole
    table into the gather's 1-D form on every use -- inside the tick, once
    per lane -- so the step takes the columns, built once per table.  A
    tuple, not one c-major vector: ``col * N + row`` overflows int32 at
    scale.  Jitted, so that the columns are written in one program, with
    no whole-table intermediate between the slice and the relayout.

    ``sharding`` pins where each column lies; for a row-sharded table, its
    1-D row sharding over the same mesh, so that every device writes its
    own block of each column from its own block of rows and holds no more.
    """
    return _columns_program(sharding)(values)


@functools.lru_cache(maxsize=16)
def _columns_program(sharding):
    def columns(values):
        return tuple(values[:, j] for j in range(values.shape[1]))
    if sharding is None:
        return jax.jit(columns)
    return jax.jit(columns, out_shardings=sharding)


def _gather_rows(cols: Tuple[Array, ...], idx: Array) -> Array:
    """Rows ``idx`` of the column table, ``idx.shape + (c,)`` like
    ``values[idx]`` on the ``(N, c)`` table."""
    return jnp.stack([col[idx] for col in cols], axis=-1)


def lane_active(state: LaneState, max_iters: int) -> Array:
    """(q,) lanes still iterating: not converged, not failed, ticks left."""
    return ~state.done & ~state.failed & (state.k < max_iters)


@scoped("miss.fit")
def _fit_predict(s: LaneState, p: LaneParams, *, tau: float,
                 growth_cap: float, max_iters: int, l: int):
    """FIT + PREDICT for every lane (shared by the solo and sharded bodies).

    Returns ``(n_pred (q, m), beta (q, m+1), r2 (q,), failed_fit (q,))``.

    Warm lanes (phase H) override the first ``l`` ticks: tick 0 jumps to
    the cached ``warm_n0`` prediction, and if that one-tick verification
    misses the bound, later warm ticks refine through the cached
    coefficients' local model (same ratio**(1/slope) correction as the cold
    loop) -- the WLS fit over a 0..l-1-row profile is meaningless, and a
    fit "failure" there must not kill the lane (``failed_fit`` is shielded
    while warm).  From tick ``l`` the lane has a full profile of its own
    warm trajectory and the ordinary fit takes over.
    """
    log_eps = jnp.log(p.epsilons.astype(jnp.float32))
    row_valid = (jnp.arange(max_iters)[None, :]
                 < s.k[:, None]).astype(jnp.float32)           # (q, max_iters)
    use_warm = p.warm & (s.k < l)                              # (q,)

    def lane_predict(prof_n, prof_loge, rv, e_lane, n_cur, le, eps_lane,
                     uw, k_lane, wn0, wbeta):
        n_hat, fit = error_model.fit_and_predict(
            prof_n, prof_loge, rv, le, tau)
        n_next = jnp.ceil(n_hat).astype(jnp.int32)
        # Local-model correction from the last iterate (see l2miss).
        slope = jnp.maximum(jnp.sum(fit.beta[1:]), 1e-3)
        ratio = jnp.maximum(e_lane / eps_lane, 1.0)
        local = jnp.ceil(
            n_cur.astype(jnp.float32) * ratio ** (1.0 / slope)
        ).astype(jnp.int32)
        n_next = jnp.maximum(n_next, local)
        # Trust region + growth guard (see l2miss.MissConfig.growth_cap).
        cap = (n_cur.astype(jnp.float32) * growth_cap).astype(
            jnp.int32) + 1
        n_next = jnp.minimum(n_next, cap)
        n_next = jnp.maximum(n_next, n_cur + 1)
        failed = fit.status == error_model.DIAG_FAILURE
        # Warm override: tick 0 takes the cached prediction wholesale; a
        # stale prediction extends via the cached slope (e_lane is the
        # measured error AT the cached n, so the ratio correction is exact
        # under the model).  The growth guard still applies.
        wslope = jnp.maximum(jnp.sum(wbeta[1:]), 1e-3)
        wlocal = jnp.ceil(
            n_cur.astype(jnp.float32) * ratio ** (1.0 / wslope)
        ).astype(jnp.int32)
        wnext = jnp.where(
            k_lane == 0, wn0,
            jnp.minimum(jnp.maximum(wlocal, n_cur + 1), cap))
        n_out = jnp.where(uw, wnext, n_next)
        beta_out = jnp.where(uw, wbeta, fit.beta)
        r2_out = jnp.where(uw, 0.0, fit.r2)
        return n_out, beta_out, r2_out, failed & ~uw

    return jax.vmap(lane_predict)(
        s.prof_n, s.prof_loge, row_valid, s.e, s.n_cur, log_eps, p.epsilons,
        use_warm, s.k, p.warm_n0, p.warm_beta)


@scoped("miss.epilogue")
def _lane_epilogue(s: LaneState, p: LaneParams, *, max_iters, active,
                   init_phase, new_keys, e_b, theta_b, n_eff, filled, buf,
                   beta, r2, failed_fit) -> LaneState:
    """TEST + the predicated state merge (shared by solo and sharded bodies)."""
    q = p.epsilons.shape[0]
    loge = jnp.maximum(jnp.log(jnp.maximum(e_b, 1e-30)), LOG_FLOOR)
    qi = jnp.arange(q)
    kq = jnp.minimum(s.k, max_iters - 1)     # frozen lanes: no-op rewrite
    prof_n = s.prof_n.at[qi, kq].set(
        jnp.where(active[:, None], n_eff.astype(jnp.float32),
                  s.prof_n[qi, kq]))
    prof_loge = s.prof_loge.at[qi, kq].set(
        jnp.where(active, loge, s.prof_loge[qi, kq]))
    done = s.done | (active & (e_b <= p.epsilons))
    failed = s.failed | (active & ~init_phase & failed_fit)
    return LaneState(
        keys=new_keys, k=s.k + 1, iters=s.iters + active.astype(jnp.int32),
        n_cur=jnp.where(active[:, None], n_eff, s.n_cur),
        filled=filled, buf=buf, prof_n=prof_n, prof_loge=prof_loge,
        e=jnp.where(active, e_b, s.e),
        theta=jnp.where(active[:, None, None], theta_b, s.theta),
        done=done, failed=failed,
        beta=jnp.where((active & ~init_phase)[:, None], beta, s.beta),
        r2=jnp.where(active & ~init_phase, r2, s.r2),
    )


def _segment_tick(cols, s, p, *, active, win_lo, win_hi, seeds, est,
                  B, n_max, n_cap, ext_cap, seg_cap, metric, use_kernel):
    """Shared-scan SAMPLE + ESTIMATE of a grouped lane block (phase I).

    ``cols`` is the table as :func:`as_columns` gives it; the packed gather
    reads each column at the packed row indices.

    The block is ``q`` lanes of ``m = 1`` -- lane g bound to group g via its
    row of the stratified slot tables.  One PACKED gather over all active
    lanes' extension windows replaces the per-lane ``lax.map`` gather, and
    one segment-aggregated moment pass replaces the shared width-bucket
    bootstrap: per-tick cost tracks the union watermark (the packed stream
    length, padded to a :func:`seg_ladder` rung), not ``q x`` the global
    max width.  Windows, slot bindings, and the (seed, absolute slot,
    replicate) weight draws are identical to the generic path, so a block
    lane's trajectory matches its solo run up to the f32 summation order of
    the moment sums (the documented sharded-pool tolerance).

    Packing: lane windows are concatenated in lane order; element j maps to
    its owner by ``searchsorted`` over the cumulative window starts.
    Zero-width lanes (frozen, parked, or converged) own no elements --
    ``side="right"`` search skips their duplicated starts -- so an inactive
    lane contributes nothing to the scan and its (guarded) zero-sum outputs
    are discarded by the predicated epilogue, exactly like the generic
    path's masked lanes.
    """
    q = p.epsilons.shape[0]
    filled0 = s.filled[:, 0]
    lo, hi = win_lo[:, 0], win_hi[:, 0]

    # ---- one packed gather over the extension windows [filled, win_hi) ----
    with jax.named_scope("miss.gather"):
        ext_w = jnp.maximum(hi - filled0, 0)   # inactive: hi <= filled -> 0
        gather_cap = min(seg_cap, q * ext_cap)
        g_rungs = _window_ladder(gather_cap,
                                 min(sampling.bucket_cap(n_max), gather_cap))
        g_total = jnp.sum(ext_w)
        g_idx = jnp.sum(g_total > jnp.asarray(g_rungs[:-1], jnp.int32))
        g_starts = jnp.cumsum(ext_w) - ext_w                   # (q,)

        def mk_gather(L):
            def branch(buf_b):
                j = jnp.arange(L, dtype=jnp.int32)
                lane_j = jnp.clip(
                    jnp.searchsorted(g_starts, j, side="right") - 1, 0,
                    q - 1)
                slot_j = filled0[lane_j] + (j - g_starts[lane_j])
                valid = j < g_total
                gidx = p.slot_idx[lane_j, 0, jnp.minimum(slot_j, n_cap - 1)]
                rows = _gather_rows(cols, gidx)                # (L, c)
                tgt = jnp.where(valid, slot_j, n_cap)          # OOB -> drop
                return buf_b.at[lane_j, 0, tgt].set(rows, mode="drop")
            return branch

        buf = jax.lax.switch(g_idx.astype(jnp.int32),
                             [mk_gather(w) for w in g_rungs], s.buf)
    filled = jnp.maximum(s.filled, win_hi)

    # ---- one segment-aggregated ESTIMATE over [win_lo, win_hi) ----
    est_w = jnp.where(active, hi - lo, 0)
    e_rungs = seg_ladder(seg_cap, n_max)
    e_total = jnp.sum(est_w)
    e_idx = jnp.sum(e_total > jnp.asarray(e_rungs[:-1], jnp.int32))
    e_starts = jnp.cumsum(est_w) - est_w
    lane_seeds = seeds[:, 0]                                   # (q,)

    def mk_est(L):
        def branch(buf_b):
            j = jnp.arange(L, dtype=jnp.int32)
            lane_j = jnp.clip(
                jnp.searchsorted(e_starts, j, side="right") - 1, 0, q - 1)
            slot_j = jnp.minimum(lo[lane_j] + (j - e_starts[lane_j]),
                                 n_cap - 1)
            valid = j < e_total
            x_j = buf_b[lane_j, 0, slot_j, 0]
            return bootstrap.segment_moment_sums(
                x_j, lane_j, slot_j, valid, lane_seeds, q, B,
                use_kernel=use_kernel)
        return branch

    with jax.named_scope("miss.estimate"):
        M, Mp = jax.lax.switch(e_idx.astype(jnp.int32),
                               [mk_est(w) for w in e_rungs], buf)
        e_b, theta_b = bootstrap.finish_lanes_moments(
            M[:, None], Mp[:, None], p.scale, p.deltas, est=est,
            est_fids=p.est_fids, metric=metric)
    return buf, filled, e_b, theta_b


def _step_body(
    cols: Tuple[Array, ...],
    offsets: Array,
    s: LaneState,
    p: LaneParams,
    *,
    est_name: Optional[str],
    B: int,
    n_min: int,
    n_max: int,
    l: int,
    tau: float,
    max_iters: int,
    n_cap: int,
    backend: str,
    metric: str,
    growth_cap: float,
    ext_cap: int,
    adaptive: bool,
    use_kernel: bool,
    gate_gather: bool,
    seg_cap: Optional[int] = None,
) -> LaneState:
    """One SAMPLE -> ESTIMATE -> FIT -> PREDICT -> TEST tick over all lanes.

    Every per-lane computation is lane-separable and predicated on the
    lane's own ``active`` flag, so a lane's trajectory is a pure function of
    its (key, sample_key, epsilon, delta, scale, est_fid) rows and its own
    tick counter -- bit-identical whether its neighbors are the same age
    (closed loop), frozen, or mid-refill (lane pool).  The ESTIMATE width
    bucket is shared -- the max watermark over *active* lanes -- which is
    statistically invisible because the counter-PRNG weight draws do not
    depend on the bucket width.

    ``seg_cap`` (phase I) switches a q-lane block of m=1 per-group lanes
    onto the SHARED-SCAN path: the tick packs every active lane's window
    into one flat stream (capacity ``seg_cap`` = the block's union
    watermark ceiling), runs ONE gather over the packed extension windows
    and ONE segment-aggregated moment pass -- per-tick cost tracks rows
    scanned, not ``q x`` the global width bucket.  Decision structure,
    windows, weights, and seeds are identical to the generic path; only
    the f32 summation order of the moment sums differs.

    ``cols`` is the table as :func:`as_columns` gives it: every row gather
    reads the 1-D columns, so no tick relayouts the table.
    """
    est = get_estimator(est_name) if est_name is not None else None
    m = offsets.shape[0] - 1
    # Deterministic balanced two-point design (Eq. 15/16): cyclic shifts give
    # every group both levels, keeping all slopes identifiable.
    l_min = min(max(int(round(l * n_max / (n_min + n_max))), 1), l - 1)
    widths = bucket_ladder(n_cap, n_max) if adaptive else (n_cap,)
    shared_slots = p.slot_idx.ndim == 2

    keys2 = jax.vmap(jax.random.split)(s.keys)                 # (q, 2, 2)
    new_keys, kest = keys2[:, 0], keys2[:, 1]
    active = lane_active(s, max_iters)                         # (q,)
    # ---- generate this iteration's n (per lane) ----
    phase = (s.k[:, None] + jnp.arange(m)[None, :]) % l        # (q, m)
    n_init = jnp.where(phase < l_min, n_min, n_max).astype(jnp.int32)
    n_pred, beta, r2, failed_fit = _fit_predict(
        s, p, tau=tau, growth_cap=growth_cap, max_iters=max_iters, l=l)
    # Warm lanes (phase H) skip the init design: every tick -- the first
    # included -- takes the prediction branch, whose first-l-ticks values
    # _fit_predict already overrode with the cached-coefficient schedule.
    init_phase = (s.k < l) & ~p.warm                           # (q,)
    n_vec = jnp.where(init_phase[:, None], n_init, n_pred)
    # Per-LANE size ceiling: ordinary pools broadcast the shared layout's
    # group sizes here (identical to the old shared clamp); a grouped block
    # clamps lane g to ITS group's rows.
    n_vec = jnp.clip(n_vec, 1, jnp.minimum(p.group_sizes, n_cap))
    # Complete-sample clamp: one iteration can extend the resident prefix
    # by at most the window; a larger predicted jump is taken over
    # several iterations (growth guard keeps it monotone).
    n_vec = jnp.minimum(n_vec, s.filled + ext_cap)
    # Frozen lanes neither grow nor gather: their window degenerates to
    # the resident prefix and every update below is predicated on
    # ``active``.
    n_vec = jnp.where(active[:, None], n_vec, s.n_cur)
    # Init probes read STACKED slot windows [filled, filled + n): two
    # probes at the same design level must be different rows or the WLS
    # fit loses its independent variation.  Their union is the prefix
    # the prediction phase (win_lo = 0) then reuses wholesale.  A window
    # that would overrun n_cap is shifted back into the resident prefix
    # (reusing rows) rather than truncated -- n_eff must never collapse
    # to an empty mask.
    win_lo = jnp.where(init_phase[:, None],
                       jnp.minimum(s.filled, n_cap - n_vec), 0)
    win_lo = jnp.where(active[:, None], win_lo, 0)
    win_hi = jnp.where(active[:, None], win_lo + n_vec,
                       jnp.minimum(s.n_cur, s.filled))
    n_eff = n_vec
    if seg_cap is not None:
        # Grouped lane block (phase I): one shared scan for the whole tick.
        seeds = prng.hash3(
            prng.hash3(p.boot_base, s.k.astype(jnp.uint32),
                       jnp.uint32(_SALT_GROUP))[:, None],
            jnp.arange(m, dtype=jnp.uint32)[None, :],
            jnp.uint32(_SALT_GROUP))                           # (q, m)
        buf, filled, e_b, theta_b = _segment_tick(
            cols, s, p, active=active, win_lo=win_lo, win_hi=win_hi,
            seeds=seeds, est=est, B=B, n_max=n_max, n_cap=n_cap,
            ext_cap=ext_cap, seg_cap=seg_cap, metric=metric,
            use_kernel=use_kernel)
        return _lane_epilogue(
            s, p, max_iters=max_iters, active=active, init_phase=init_phase,
            new_keys=new_keys, e_b=e_b, theta_b=theta_b, n_eff=n_eff,
            filled=filled, buf=buf, beta=beta, r2=r2, failed_fit=failed_fit)
    # ---- extend the carried nested samples by the window only ----
    # One lane's window gather: (m, ext_cap) rows past the watermark,
    # scattered into the lane's carried buffer (OOB targets dropped).
    def _lane_gather(buf_l, filled_l, hi_l, slot_idx_l):
        slots = filled_l[:, None] + jnp.arange(
            ext_cap, dtype=jnp.int32)[None, :]                 # (m, ext)
        valid = slots < hi_l[:, None]
        clipped = jnp.minimum(slots, n_cap - 1)
        gidx = jnp.take_along_axis(slot_idx_l, clipped, axis=1)
        new_rows = _gather_rows(cols, gidx)                    # (m, ext, c)
        tgt = jnp.where(valid, slots, n_cap)                   # OOB -> dropped
        return buf_l.at[jnp.arange(m)[:, None], tgt].set(
            new_rows, mode="drop")

    with jax.named_scope("miss.gather"):
        if gate_gather:
            # Per-lane lax.cond (a REAL branch under lax.map, not the
            # execute-both of vmapped control flow): frozen/parked lanes
            # skip the gather entirely, so a tick's HBM row traffic is
            # bounded by sum(active) * ext_cap instead of q * ext_cap.
            # Exact skip: an inactive lane's window degenerates to the
            # resident prefix (win_hi <= filled above), so its gather would
            # scatter nothing -- gated and ungated buffers are bit-identical.
            def _one(args):
                buf_l, filled_l, hi_l, act_l = args[:4]
                slot_idx_l = p.slot_idx if shared_slots else args[4]
                return jax.lax.cond(
                    act_l,
                    lambda _: _lane_gather(buf_l, filled_l, hi_l, slot_idx_l),
                    lambda _: buf_l, 0)

            operands = (s.buf, s.filled, win_hi, active)
            if not shared_slots:
                operands = operands + (p.slot_idx,)
            buf = jax.lax.map(_one, operands)
        elif shared_slots:
            buf = jax.lax.map(
                lambda a: _lane_gather(a[0], a[1], a[2], p.slot_idx),
                (s.buf, s.filled, win_hi))
        else:
            buf = jax.lax.map(
                lambda a: _lane_gather(*a),
                (s.buf, s.filled, win_hi, p.slot_idx))
    filled = jnp.maximum(s.filled, win_hi)
    # ---- bootstrap estimate on the active width bucket ----
    # Bucket = max watermark over ACTIVE lanes: frozen lanes' (possibly
    # larger) windows are excluded -- their estimate output is discarded
    # below, so computing it on a truncated mask is harmless.
    needed = jnp.maximum(
        jnp.max(jnp.where(active[:, None], win_hi, 0)), 1)
    w_arr = jnp.asarray(widths[:-1], jnp.int32)
    b_idx = jnp.sum(needed > w_arr).astype(jnp.int32)
    seeds = prng.hash3(
        prng.hash3(p.boot_base, s.k.astype(jnp.uint32),
                   jnp.uint32(_SALT_GROUP))[:, None],
        jnp.arange(m, dtype=jnp.uint32)[None, :],
        jnp.uint32(_SALT_GROUP))                               # (q, m)

    def make_branch(width):
        def branch(buf_b, lo_b, hi_b, seeds_b, kest_b):
            bw = jax.lax.slice_in_dim(buf_b, 0, width, axis=2)
            pos = jnp.arange(width, dtype=jnp.int32)[None, None, :]
            msk = ((pos >= lo_b[:, :, None]) &
                   (pos < hi_b[:, :, None])).astype(jnp.float32)
            # Frozen/parked lanes skip the bootstrap entirely (their output
            # is discarded by the predicated merges below) -- a pool tick
            # costs its ACTIVE lanes, not its capacity.
            if est is None:
                if backend != "poisson":
                    raise ValueError(
                        "per-lane estimators (est_name=None) require the "
                        "counter-PRNG poisson backend")
                return bootstrap.estimate_error_lanes_het(
                    bw, msk, seeds_b, p.est_fids, p.scale, p.deltas, B=B,
                    metric=metric, use_kernel=use_kernel,
                    lane_active=active)
            if backend == "poisson":
                return bootstrap.estimate_error_lanes(
                    est, bw, msk, seeds_b, p.scale, p.deltas, B=B,
                    metric=metric, use_kernel=use_kernel,
                    lane_active=active)
            return jax.vmap(
                lambda smp, mk, kk, sc, d: bootstrap.estimate_error(
                    est, smp, mk, sc, kk, d, B=B, backend=backend,
                    metric=metric))(bw, msk, kest_b, p.scale, p.deltas)
        return branch

    with jax.named_scope("miss.estimate"):
        e_b, theta_b = jax.lax.switch(
            b_idx, [make_branch(w) for w in widths],
            buf, win_lo, win_hi, seeds, kest)
    return _lane_epilogue(
        s, p, max_iters=max_iters, active=active, init_phase=init_phase,
        new_keys=new_keys, e_b=e_b, theta_b=theta_b, n_eff=n_eff,
        filled=filled, buf=buf, beta=beta, r2=r2, failed_fit=failed_fit)


# ---------------------------------------------------------------------------
# Sharded step (DESIGN.md phase G): the same tick over S row shards
# ---------------------------------------------------------------------------

class ShardSpec(NamedTuple):
    """Device-side shard layout tables for the sharded step.

    ``alloc[s, i, n]`` counts how many of the first ``n`` logical sample
    slots of group i live in shard s's buffer segment (the cumulative
    ownership table of :class:`~.sampling.ShardLayout`); ``cap_groups[i]``
    is group i's total logical slot capacity.  Under the mesh step the
    leading axis is sharded -- each device sees its own ``(1, m, n_cap+1)``
    alloc slice -- while the solo-emulation path keeps all S tables
    resident.
    """
    alloc: Array        # (S, m, n_cap + 1) int32
    cap_groups: Array   # (m,) int32


def make_shard_spec(layout: "sampling.ShardLayout") -> ShardSpec:
    """Lift a host :class:`~.sampling.ShardLayout` onto the device."""
    return ShardSpec(alloc=jnp.asarray(layout.alloc, jnp.int32),
                     cap_groups=jnp.asarray(layout.cap_groups, jnp.int32))


def resolve_seg_window(n_cap: int, n_max: int, data_shards: int,
                       ext_cap: Optional[int] = None) -> int:
    """Per-SEGMENT extension window of the sharded step.

    The sharded analogue of :func:`resolve_ext_cap`: ``ext_cap`` keeps its
    GLOBAL meaning (the most logical slots one lane-tick may grow), and
    each shard's segment gets its proportional SHARE of that window plus
    an imbalance slack -- NOT the full global window per segment, which
    would multiply one tick's gather traffic by the shard count.  The
    growth clamp in the step body makes any window size safe: it advances
    the logical watermark only as far as every segment's local share fits
    its window, so an unusually skewed stretch of the alloc tables costs
    extra refinement ticks, never missing rows.
    """
    if n_cap % data_shards:
        raise ValueError(
            f"n_cap={n_cap} must divide by data_shards={data_shards}")
    cap_s = n_cap // data_shards
    if n_max > cap_s:
        raise ValueError(
            f"n_max={n_max} exceeds one shard segment ({cap_s} slots); "
            f"raise n_cap or lower data_shards")
    ext_global = resolve_ext_cap(n_cap, n_max, ext_cap)
    share = -(-ext_global // data_shards)
    return min(cap_s, share + max(share // 4, 32))


def _sharded_step_body(
    cols: Tuple[Array, ...],  # c x (N,) global | (R,) per-device slice (mesh)
    s: LaneState,
    p: LaneParams,      # slot_idx (S, m, cap_s) | (1, m, cap_s) local slice
    spec: ShardSpec,
    *,
    est_name: Optional[str],
    B: int,
    n_min: int,
    n_max: int,
    l: int,
    tau: float,
    max_iters: int,
    n_cap: int,
    metric: str,
    growth_cap: float,
    seg_window: int,
    use_kernel: bool,
    data_shards: int,
    axis_name: Optional[str],
) -> LaneState:
    """One tick with the buffer slot axis segmented over S row shards.

    Identical decision structure to :func:`_step_body`, with SAMPLE and the
    bootstrap moment pass running per shard segment: each segment gathers
    its own extension window from its own rows (its slice of the 1-Lipschitz
    ``alloc`` tables says how many slots it owns), computes RAW replicate
    moment sums with per-(lane, group, shard) counter streams, and the sums
    are combined -- ``lax.psum`` under the mesh (``axis_name="data"``), a
    sequential left fold in shard order on the solo-emulation path
    (``axis_name=None``).  A CPU host mesh's psum reduces in exactly that
    device order, which is the determinism anchor making the two paths
    bit-equal at the same static ``data_shards`` (DESIGN.md phase G).  Only
    ONE collective crosses the interconnect per tick -- the ``(q, m, B,
    3)``/``(q, m, 3)`` moment psum: the growth clamp folds the replicated
    alloc stack locally on every device, and everything else -- FIT,
    PREDICT, TEST, the whole LaneState except ``buf`` -- is replicated.

    ``cols`` is the table (each device's row block of it under the mesh)
    as :func:`as_columns` gives it, so the segment gathers read the
    columns and no tick relayouts a shard.
    """
    est = get_estimator(est_name) if est_name is not None else None
    cap_s = n_cap // data_shards
    m = spec.cap_groups.shape[0]
    gi = jnp.arange(m)[None, :]
    l_min = min(max(int(round(l * n_max / (n_min + n_max))), 1), l - 1)
    # Per-SEGMENT width ladder: a segment window holds ~1/S of a lane's
    # rows, so the bottom rung is the segment's SHARE of n_max, not n_max
    # itself -- otherwise the ladder degenerates to [cap_s] and every
    # segment pays its full capacity in ESTIMATE.  Rungs are raw shares
    # with midpoints, not pow2 buckets: the ladder is static per (n_cap,
    # n_max, S) config, so there is no signature blowup to guard against,
    # and the tight rungs are where sharding beats the 1-device pool's
    # coarse pow2 buckets on padding waste.
    # Ladder floor: the n_MIN share, not the n_max share.  The bootstrap is
    # hash-throughput-bound (~B Poisson draws per gathered slot), so a lane
    # probing at n_min must not pay n_max-share rungs across all S segments
    # -- that alone prices a 300-row window at 600 slots of hashing.
    seg_share = -(-n_max // data_shards)
    seg_base = max(min(seg_share, -(-n_min // data_shards)), 32)
    seg_widths = _window_ladder(cap_s, min(seg_base, cap_s))
    w_arr = jnp.asarray(seg_widths[:-1], jnp.int32)

    keys2 = jax.vmap(jax.random.split)(s.keys)                 # (q, 2, 2)
    new_keys = keys2[:, 0]
    active = lane_active(s, max_iters)                         # (q,)
    phase = (s.k[:, None] + jnp.arange(m)[None, :]) % l        # (q, m)
    n_init = jnp.where(phase < l_min, n_min, n_max).astype(jnp.int32)
    n_pred, beta, r2, failed_fit = _fit_predict(
        s, p, tau=tau, growth_cap=growth_cap, max_iters=max_iters, l=l)
    # Phase H: warm lanes ride the prediction branch from tick 0 (see the
    # solo body); the cross-shard growth clamp below spreads an oversized
    # cached jump over extra ticks exactly as it does a cold PREDICT jump.
    init_phase = (s.k < l) & ~p.warm                           # (q,)
    n_vec = jnp.where(init_phase[:, None], n_init, n_pred)
    n_vec = jnp.clip(n_vec, 1, spec.cap_groups[None, :])

    # ---- cross-shard growth clamp ----
    # One tick extends each segment by at most ``seg_window`` LOCAL slots;
    # the logical watermark may only grow while every segment's share of
    # the growth fits its window.  seg_window is the proportional share of
    # the global extension window plus slack (resolve_seg_window), so the
    # clamp normally grants the full init design in one tick; a skewed
    # alloc stretch just spreads the growth over extra ticks.
    def seg_headroom(alloc_sm):                                # (m, n_cap+1)
        lfill = alloc_sm[gi, s.filled]                         # (q, m)
        hi = jax.vmap(
            lambda a, v: jnp.searchsorted(a, v, side="right"),
            in_axes=(0, 1), out_axes=1)(alloc_sm, lfill + seg_window)
        return hi.astype(jnp.int32) - 1 - s.filled             # (q, m)

    # alloc is replicated (a few KB per shard), so EVERY device folds the
    # full (S, m, n_cap+1) stack locally -- no pmin collective; the psum
    # on the moment sums is the single barrier a tick crosses.
    allowed = jnp.min(jax.vmap(seg_headroom)(spec.alloc), axis=0)
    # ``allowed`` is GROWTH.  An init window stacks past the watermark, so
    # the window itself is the growth; a prediction window is the prefix
    # [0, n), which grows the watermark by n - filled.
    n_vec = jnp.minimum(n_vec, jnp.where(init_phase[:, None], allowed,
                                         s.filled + allowed))
    n_vec = jnp.where(active[:, None], n_vec, s.n_cur)
    win_lo = jnp.where(init_phase[:, None],
                       jnp.minimum(s.filled, spec.cap_groups[None, :] - n_vec),
                       0)
    win_lo = jnp.where(active[:, None], win_lo, 0)
    win_hi = jnp.where(active[:, None], win_lo + n_vec,
                       jnp.minimum(s.n_cur, s.filled))
    n_eff = n_vec
    filled = jnp.maximum(s.filled, win_hi)

    seeds = prng.hash3(
        prng.hash3(p.boot_base, s.k.astype(jnp.uint32),
                   jnp.uint32(_SALT_GROUP))[:, None],
        jnp.arange(m, dtype=jnp.uint32)[None, :],
        jnp.uint32(_SALT_GROUP))                               # (q, m)

    def seg_tick(buf_seg, alloc_sm, table_sm, seg_id):
        """Gather + RAW moment sums for ONE shard segment.

        ``buf_seg (q, m, cap_s, c)`` the segment's slice of the carried
        buffer, ``alloc_sm (m, n_cap+1)`` its ownership table, ``table_sm
        (m, cap_s)`` its slot->row binding, ``seg_id`` uint32 shard index.
        """
        lfill = alloc_sm[gi, s.filled]                         # (q, m)
        llo = alloc_sm[gi, win_lo]
        lhi = alloc_sm[gi, win_hi]

        gather_widths = _window_ladder(seg_window,
                                       max(seg_window // 4, 32))
        gw_arr = jnp.asarray(gather_widths[:-1], jnp.int32)

        def lane_gather(args):
            buf_l, f_l, h_l, act_l = args

            def mk_grow(W):
                # Gather width is laddered like the ESTIMATE rungs: an
                # extension tick usually grows a segment by far less than
                # the full seg_window (the init jump's worst case), and the
                # values gather + buf scatter price the full W regardless
                # of how many slots land (invalid rows drop).  The buffer
                # contents are identical at any W >= the lane's need.
                def grow(_):
                    slots = f_l[:, None] + jnp.arange(
                        W, dtype=jnp.int32)[None, :]           # (m, W)
                    valid = slots < h_l[:, None]
                    clipped = jnp.minimum(slots, cap_s - 1)
                    gidx = jnp.take_along_axis(table_sm, clipped, axis=1)
                    new_rows = _gather_rows(cols, gidx)        # (m, W, c)
                    tgt = jnp.where(valid, slots, cap_s)       # OOB -> drop
                    return buf_l.at[jnp.arange(m)[:, None], tgt].set(
                        new_rows, mode="drop")
                return grow

            def grow_any(_):
                need_l = jnp.max(jnp.maximum(h_l - f_l, 0))
                gb = jnp.sum(need_l > gw_arr).astype(jnp.int32)
                return jax.lax.switch(
                    gb, [mk_grow(w) for w in gather_widths], 0)

            return jax.lax.cond(act_l, grow_any, lambda _: buf_l, 0)

        with jax.named_scope("miss.gather"):
            buf_new = jax.lax.map(lane_gather, (buf_seg, lfill, lhi, active))
        seeds_s = prng.hash3(seeds, seg_id, jnp.uint32(_SALT_SHARD))
        with jax.named_scope("miss.estimate"):
            if use_kernel:
                # Kernel path: prefix semantics, one shared rung -- the tile
                # grid is what gates per-lane cost there.
                needed = jnp.maximum(
                    jnp.max(jnp.where(active[:, None], lhi, 0)), 1)
                b_idx = jnp.sum(needed > w_arr).astype(jnp.int32)

                def make_branch(width):
                    def branch(buf_b, lo_b, hi_b, seeds_b):
                        bw = jax.lax.slice_in_dim(buf_b, 0, width, axis=2)
                        pos = jnp.arange(width, dtype=jnp.int32)[
                            None, None, :]
                        msk = ((pos >= lo_b[:, :, None]) &
                               (pos < hi_b[:, :, None])).astype(jnp.float32)
                        return bootstrap.lane_moment_sums(
                            bw[..., 0].astype(jnp.float32), msk, seeds_b, B,
                            use_kernel=True, lane_active=active)
                    return branch

                M_s, Mp_s = jax.lax.switch(
                    b_idx, [make_branch(w) for w in seg_widths],
                    buf_new, llo, lhi, seeds_s)
            else:
                # jnp path: windowed gather at per-lane rungs -- see
                # bootstrap.windowed_lane_moment_sums for why both matter.
                M_s, Mp_s = bootstrap.windowed_lane_moment_sums(
                    buf_new[..., 0], llo, lhi, seeds_s, B, seg_widths,
                    lane_active=active)
        return buf_new, M_s, Mp_s

    if axis_name is None:
        segs = [
            seg_tick(
                jax.lax.slice_in_dim(
                    s.buf, si * cap_s, (si + 1) * cap_s, axis=2),
                spec.alloc[si], p.slot_idx[si], jnp.uint32(si))
            for si in range(data_shards)
        ]
        buf = jnp.concatenate([t[0] for t in segs], axis=2)
        # Sequential left fold in shard order: the reduction order a host
        # mesh's psum executes, which is what makes the mesh step bit-equal
        # to this solo reference (DESIGN.md phase G).
        M, Mp = segs[0][1], segs[0][2]
        for t in segs[1:]:
            M = M + t[1]
            Mp = Mp + t[2]
    else:
        sid = jax.lax.axis_index(axis_name)
        buf, M_s, Mp_s = seg_tick(s.buf, spec.alloc[sid], p.slot_idx[0],
                                  sid.astype(jnp.uint32))
        with jax.named_scope("miss.psum"):
            M = jax.lax.psum(M_s, axis_name)
            Mp = jax.lax.psum(Mp_s, axis_name)

    with jax.named_scope("miss.estimate"):
        e_b, theta_b = bootstrap.finish_lanes_moments(
            M, Mp, p.scale, p.deltas, est=est, est_fids=p.est_fids,
            metric=metric)
    return _lane_epilogue(
        s, p, max_iters=max_iters, active=active, init_phase=init_phase,
        new_keys=new_keys, e_b=e_b, theta_b=theta_b, n_eff=n_eff,
        filled=filled, buf=buf, beta=beta, r2=r2, failed_fit=failed_fit)


def make_sharded_lane_params(
    layout: "sampling.ShardLayout",
    scale: Array,
    keys: Array,
    epsilons: Array,
    deltas: Array,
    sample_key: Array,
    est_fids: Optional[Array] = None,
    *,
    local_rows: bool,
    warm: Optional[Array] = None,
    warm_n0: Optional[Array] = None,
    warm_beta: Optional[Array] = None,
) -> LaneParams:
    """Per-lane parameters for the sharded step: stacked per-shard tables.

    All lanes share ONE ``(2,)`` sample key (the server epoch policy) --
    per-lane bindings are not supported on the sharded path.  With
    ``local_rows=True`` slot tables index each device's values slice (the
    mesh path); ``False`` yields global rows into the unsharded/padded
    table (the solo-emulation path).  Bootstrap seed bases are derived
    exactly as :func:`make_lane_params` does, so a lane's streams match its
    solo run.
    """
    if sample_key.ndim != 1:
        raise ValueError("sharded lanes require one shared (2,) sample key")
    q = epsilons.shape[0]
    slot_idx = sampling.sharded_slot_tables(
        sample_key, layout, local_rows=local_rows)
    boot_base = jax.vmap(lane_boot_seed)(keys)
    if est_fids is None:
        est_fids = jnp.zeros((q,), jnp.int32)
    m = layout.cap_groups.shape[0]
    w, wn0, wb = resolve_warm_rows(q, m, warm, warm_n0, warm_beta)
    return LaneParams(
        scale=jnp.asarray(scale), epsilons=jnp.asarray(epsilons, jnp.float32),
        deltas=jnp.asarray(deltas, jnp.float32),
        est_fids=jnp.asarray(est_fids, jnp.int32), boot_base=boot_base,
        slot_idx=slot_idx, warm=w, warm_n0=wn0, warm_beta=wb,
        group_sizes=jnp.broadcast_to(
            jnp.asarray(layout.cap_groups, jnp.int32)[None, :], (q, m)))


_SHARD_STEP_STATICS = (
    "est_name", "B", "n_min", "n_max", "l", "tau", "max_iters", "n_cap",
    "metric", "growth_cap", "seg_window", "use_kernel", "data_shards",
)


def make_sharded_step(mesh, *, num_ticks: int = 1, **statics):
    """Compile the mesh-native multi-tick step: ``shard_map`` over "data".

    ``statics`` are the :data:`_SHARD_STEP_STATICS` (``seg_window`` already
    resolved via :func:`resolve_seg_window`).  Per device and tick: its
    row block of each column, its buffer segment, its slot table, and ONE
    collective (the moment-sums ``psum``; the growth clamp is local).
    Returns ``step(cols, state, params, shard_spec) -> state`` preserving
    input shardings, where ``cols`` is :func:`as_columns` of the
    row-sharded table, each column row-sharded over ``mesh``; every
    LaneState leaf except ``buf`` stays replicated.

    Memoized on ``(mesh, num_ticks, statics)``: callers that rebuild pools
    (serving rebuilds, the tests) share ONE jitted program instead of
    recompiling per instance -- a mesh step compile is seconds, a pool
    lifetime often is not.  The memo is a small LRU (a long-lived server
    cycling many pool configurations must not pin every program it ever
    compiled); its occupancy is observable via
    :func:`sharded_step_cache_size` (surfaced in ``LanePool.stats()``).
    """
    return _make_sharded_step(mesh, num_ticks,
                              tuple(sorted(statics.items())))


def sharded_psum_bytes(q: int, m: int, B: int) -> int:
    """Bytes each device adds to one tick's moment ``psum``s in the sharded
    step of ``q`` lanes over ``m`` groups: the float32 replicate sums ``M_s
    (q, m, B, 3)`` and plain sums ``Mp_s (q, m, 3)``."""
    return 4 * q * m * 3 * (B + 1)


def sharded_step_cache_size() -> int:
    """Entries resident in the :func:`make_sharded_step` memo LRU."""
    return _make_sharded_step.cache_info().currsize


_SHARDED_STEP_CACHE_MAX = 16


@functools.lru_cache(maxsize=_SHARDED_STEP_CACHE_MAX)
def _make_sharded_step(mesh, num_ticks, statics_items):
    statics = dict(statics_items)
    from jax.sharding import PartitionSpec as PS

    spec = dict(statics, axis_name="data")
    st_specs = LaneState(
        keys=PS(), k=PS(), iters=PS(), n_cur=PS(), filled=PS(),
        buf=PS(None, None, "data", None), prof_n=PS(), prof_loge=PS(),
        e=PS(), theta=PS(), done=PS(), failed=PS(), beta=PS(), r2=PS())
    pr_specs = LaneParams(
        scale=PS(), epsilons=PS(), deltas=PS(), est_fids=PS(),
        boot_base=PS(), slot_idx=PS("data", None, None),
        warm=PS(), warm_n0=PS(), warm_beta=PS(), group_sizes=PS())
    # alloc replicated: every device needs the full stack for the local
    # growth clamp (and its own shard's table via axis_index).
    sp_specs = ShardSpec(alloc=PS(), cap_groups=PS())

    def body(cols, state, params, sspec):
        def one(st):
            return _sharded_step_body(cols, st, params, sspec, **spec)
        if num_ticks == 1:
            return one(state)
        return jax.lax.fori_loop(0, num_ticks, lambda _, st: one(st), state)

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PS("data"), st_specs, pr_specs, sp_specs),
        out_specs=st_specs, check_vma=False)
    return jax.jit(sm)


_STEP_STATICS = (
    "est_name", "B", "n_min", "n_max", "l", "tau", "max_iters", "n_cap",
    "backend", "metric", "growth_cap", "ext_cap", "adaptive", "use_kernel",
    "gate_gather",
)


@partial(jax.jit,
         static_argnames=_STEP_STATICS + ("num_ticks", "data_shards",
                                          "seg_window", "seg_cap"))
def fused_step(
    values: Tuple[Array, ...],
    offsets: Array,
    state: LaneState,
    params: LaneParams,
    shard_spec: Optional[ShardSpec] = None,
    *,
    est_name: Optional[str] = None,
    B: int = 500,
    n_min: int = 100,
    n_max: int = 200,
    l: int = 10,
    tau: float = 1e-3,
    max_iters: int = 32,
    n_cap: int = 1 << 16,
    backend: str = "poisson",
    metric: str = "l2",
    growth_cap: float = 8.0,
    ext_cap: Optional[int] = None,
    adaptive: bool = True,
    use_kernel: bool = False,
    gate_gather: bool = True,
    data_shards: int = 1,
    seg_window: Optional[int] = None,
    seg_cap: Optional[int] = None,
    num_ticks: int = 1,
) -> LaneState:
    """Host-callable resumable step: ``num_ticks`` iterations, one dispatch.

    The same body the closed loop runs; converged/failed/exhausted lanes
    freeze via predicated updates, so ticking past a lane's convergence is
    harmless (its state no longer changes) and a multi-tick dispatch never
    needs a mid-window host check.  ``est_name=None`` selects each lane's
    estimator from ``params.est_fids`` (moment family only).

    ``values`` is the table as :func:`as_columns` gives it -- a tuple of
    ``c`` 1-D ``(N,)`` columns, built once per table, never per call -- so
    the row gathers read the columns and the compiled step holds no
    whole-table relayout.

    ``data_shards > 1`` runs the SHARDED body (phase G) on one device --
    the solo-emulation reference whose answers the mesh step
    (:func:`make_sharded_step`) reproduces bit-equal.  It requires a
    ``shard_spec`` (:func:`make_shard_spec`), stacked sharded slot tables
    (:func:`make_sharded_lane_params` with ``local_rows=False``), and the
    poisson backend.  ``ext_cap`` keeps its global meaning and is resolved
    to a per-segment window via :func:`resolve_seg_window`; ``seg_window``
    bypasses the resolution with an exact per-segment value (how the pool's
    ``mesh=False`` path reuses the spec its mesh twin compiled with).

    ``seg_cap`` (phase I) selects the grouped lane BLOCK path: ``q`` lanes
    of ``m = 1``, each bound to one group of a stratified sample store
    (:func:`make_group_lane_params`), ticked with ONE packed gather and ONE
    segment-aggregated moment pass whose cost tracks the union watermark.
    Pass :func:`grouped_seg_cap` of the block's offsets; requires the
    adaptive poisson path, a moment-family estimator, single-shard data,
    and dummy ``[0, N]`` step offsets (the per-group sizes live in
    ``params.group_sizes``).
    """
    if seg_window is not None and data_shards == 1:
        raise ValueError("seg_window applies to the sharded step only")
    if not isinstance(values, tuple):
        raise TypeError("values must be as_columns(values): the step takes "
                        "the table as a tuple of 1-D columns")
    if seg_cap is not None:
        if data_shards > 1:
            raise ValueError("seg_cap (grouped blocks) is single-shard only")
        if backend != "poisson" or not adaptive:
            raise ValueError(
                "grouped blocks require the adaptive poisson path")
        if offsets.shape[0] != 2:
            raise ValueError(
                "a grouped block is q lanes of m=1 (one lane per group); "
                "pass the dummy [0, N] step offsets")
        if params.slot_idx.ndim != 3:
            raise ValueError(
                "grouped blocks need per-lane stratified slot tables "
                "(make_group_lane_params)")
        if est_name is not None:
            moment_family_index(est_name)   # raises for non-moment ests
    if data_shards > 1:
        if shard_spec is None:
            raise ValueError("data_shards > 1 requires a shard_spec")
        if backend != "poisson" or not adaptive:
            raise ValueError(
                "the sharded step supports the adaptive poisson path only")
        if params.slot_idx.ndim != 3 or params.slot_idx.shape[0] != data_shards:
            raise ValueError(
                "sharded lanes need stacked (S, m, seg_cap) slot tables "
                "(make_sharded_lane_params)")
        sspec = dict(
            est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
            max_iters=max_iters, n_cap=n_cap, metric=metric,
            growth_cap=growth_cap,
            seg_window=(seg_window if seg_window is not None else
                        resolve_seg_window(n_cap, n_max, data_shards,
                                           ext_cap)),
            use_kernel=use_kernel, data_shards=data_shards, axis_name=None)
        if num_ticks == 1:
            return _sharded_step_body(values, state, params, shard_spec,
                                      **sspec)
        return jax.lax.fori_loop(
            0, num_ticks,
            lambda _, st: _sharded_step_body(values, st, params, shard_spec,
                                             **sspec),
            state)
    ext_cap = resolve_ext_cap(n_cap, n_max, ext_cap)
    spec = dict(
        est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
        max_iters=max_iters, n_cap=n_cap, backend=backend, metric=metric,
        growth_cap=growth_cap, ext_cap=ext_cap, adaptive=adaptive,
        use_kernel=use_kernel, gate_gather=gate_gather, seg_cap=seg_cap)
    if num_ticks == 1:
        return _step_body(values, offsets, state, params, **spec)
    return jax.lax.fori_loop(
        0, num_ticks,
        lambda _, st: _step_body(values, offsets, st, params, **spec),
        state)


def lanes_result(state: LaneState) -> FusedResult:
    """Project the carried state onto the public result contract."""
    max_iters = state.prof_loge.shape[1]
    row_live = (jnp.arange(max_iters)[None, :] < state.iters[:, None])
    return FusedResult(
        n=state.n_cur, error=state.e, theta=state.theta,
        iterations=state.iters, success=state.done, failed=state.failed,
        beta=state.beta, r2=state.r2, profile_n=state.prof_n,
        profile_e=jnp.exp(state.prof_loge) * row_live,
        rows_sampled=jnp.sum(state.filled, axis=1),
    )


@partial(jax.jit, static_argnames=_SHARD_STEP_STATICS)
def _sharded_lanes_closed(
    values: Array,
    shard_spec: ShardSpec,
    slot_tables: Array,   # (S, m, seg_cap) global-row tables
    scale: Array,
    keys: Array,
    epsilons: Array,
    deltas: Array,
    est_fids: Array,
    *,
    est_name: Optional[str],
    B: int,
    n_min: int,
    n_max: int,
    l: int,
    tau: float,
    max_iters: int,
    n_cap: int,
    metric: str,
    growth_cap: float,
    seg_window: int,
    use_kernel: bool,
    data_shards: int,
) -> FusedResult:
    """The closed loop over :func:`_sharded_step_body` (solo emulation) on
    the ``(N, c)`` table, whose columns it builds once, outside the loop."""
    m = shard_spec.cap_groups.shape[0]
    boot_base = jax.vmap(lane_boot_seed)(keys)
    q = epsilons.shape[0]
    w, wn0, wb = resolve_warm_rows(q, m, None, None, None)
    params = LaneParams(
        scale=jnp.asarray(scale), epsilons=jnp.asarray(epsilons, jnp.float32),
        deltas=jnp.asarray(deltas, jnp.float32),
        est_fids=jnp.asarray(est_fids, jnp.int32), boot_base=boot_base,
        slot_idx=slot_tables, warm=w, warm_n0=wn0, warm_beta=wb,
        group_sizes=jnp.broadcast_to(
            shard_spec.cap_groups[None, :], (q, m)))
    p_dim = (get_estimator(est_name).out_dim(values.shape[1])
             if est_name is not None else 1)
    state0 = init_lane_state(
        keys, m, n_cap=n_cap, c_dim=values.shape[1], p_dim=p_dim,
        n_min=n_min, max_iters=max_iters, dtype=values.dtype)
    spec = dict(
        est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
        max_iters=max_iters, n_cap=n_cap, metric=metric,
        growth_cap=growth_cap, seg_window=seg_window, use_kernel=use_kernel,
        data_shards=data_shards, axis_name=None)
    cols = as_columns(values)     # once per call, outside the loop
    state = jax.lax.while_loop(
        lambda st: jnp.any(lane_active(st, max_iters)),
        lambda st: _sharded_step_body(cols, st, params, shard_spec, **spec),
        state0)
    return lanes_result(state)


def fused_l2miss_lanes(
    values: Array,        # (N, c) group-sorted rows -- SHARED across lanes
    offsets: Array,       # (m + 1,) -- shared
    scale: Array,         # (q, m)
    keys: Array,          # (q, 2) per-lane bootstrap keys
    epsilons: Array,      # (q,)
    deltas: Array,        # (q,)
    sample_keys: Optional[Array] = None,  # None | (2,) shared | (q, 2)
    est_fids: Optional[Array] = None,     # (q,) when est_name is None
    warm_n0: Optional[Array] = None,      # (q, m) warm-start predictions
    warm_beta: Optional[Array] = None,    # (q, m+1) cached coefficients
    *,
    data_shards: int = 1,
    shard_layout: Optional["sampling.ShardLayout"] = None,
    est_name: Optional[str] = "avg",
    B: int = 500,
    n_min: int = 100,
    n_max: int = 200,
    l: int = 10,
    tau: float = 1e-3,
    max_iters: int = 32,
    n_cap: int = 1 << 16,
    backend: str = "poisson",
    metric: str = "l2",
    growth_cap: float = 8.0,
    ext_cap: Optional[int] = None,
    adaptive: bool = True,
    use_kernel: bool = False,
    gate_gather: bool = True,
) -> FusedResult:
    """q query lanes, one resident table, one while_loop (SS7 phase C/D).

    ``data_shards > 1`` selects the SHARDED step body (phase G) run on one
    device -- the solo reference for mesh parity.  It needs a shared
    ``(2,)`` sample key (defaults to ``keys[0]`` when q == 1) and the
    adaptive poisson path; ``shard_layout`` (optional) skips rebuilding the
    host layout tables, and ``ext_cap`` becomes the per-segment window.

    ``warm_n0``/``warm_beta`` (phase H) start every lane from a cached
    prediction instead of the init design -- the closed-loop twin of a
    pool's warm splice, used by the warm-parity tests.  Unsharded path
    only; a sharded pool takes warm rows through its splice instead.
    """
    if warm_n0 is not None or warm_beta is not None:
        if (warm_n0 is None) != (warm_beta is None):
            raise ValueError("warm_n0 and warm_beta come together")
        if data_shards > 1:
            raise ValueError(
                "warm start on the closed sharded loop is not supported; "
                "use a sharded LanePool splice instead")
    if data_shards > 1:
        if backend != "poisson" or not adaptive:
            raise ValueError(
                "the sharded loop supports the adaptive poisson path only")
        if sample_keys is None:
            if keys.shape[0] != 1:
                raise ValueError(
                    "sharded lanes require one shared (2,) sample key")
            sample_keys = keys[0]
        if sample_keys.ndim != 1:
            raise ValueError(
                "sharded lanes require one shared (2,) sample key")
        layout = shard_layout if shard_layout is not None else (
            sampling.ShardLayout.build(
                np.asarray(offsets), n_cap=n_cap, num_shards=data_shards))
        tables = sampling.sharded_slot_tables(
            sample_keys, layout, local_rows=False)
        q = epsilons.shape[0]
        if est_fids is None:
            est_fids = jnp.zeros((q,), jnp.int32)
        return _sharded_lanes_closed(
            values, make_shard_spec(layout), tables, scale, keys, epsilons,
            deltas, est_fids,
            est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
            max_iters=max_iters, n_cap=n_cap, metric=metric,
            growth_cap=growth_cap,
            seg_window=resolve_seg_window(n_cap, n_max, data_shards, ext_cap),
            use_kernel=use_kernel, data_shards=data_shards)
    return _fused_l2miss_lanes1(
        values, offsets, scale, keys, epsilons, deltas, sample_keys, est_fids,
        warm_n0, warm_beta,
        est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
        max_iters=max_iters, n_cap=n_cap, backend=backend, metric=metric,
        growth_cap=growth_cap, ext_cap=ext_cap, adaptive=adaptive,
        use_kernel=use_kernel, gate_gather=gate_gather)


@partial(jax.jit, static_argnames=_STEP_STATICS)
def _fused_l2miss_lanes1(
    values: Array,        # (N, c) group-sorted rows -- SHARED across lanes
    offsets: Array,       # (m + 1,) -- shared
    scale: Array,         # (q, m)
    keys: Array,          # (q, 2) per-lane bootstrap keys
    epsilons: Array,      # (q,)
    deltas: Array,        # (q,)
    sample_keys: Optional[Array] = None,  # None | (2,) shared | (q, 2)
    est_fids: Optional[Array] = None,     # (q,) when est_name is None
    warm_n0: Optional[Array] = None,      # (q, m) warm-start predictions
    warm_beta: Optional[Array] = None,    # (q, m+1) cached coefficients
    *,
    est_name: Optional[str] = "avg",
    B: int = 500,
    n_min: int = 100,
    n_max: int = 200,
    l: int = 10,
    tau: float = 1e-3,
    max_iters: int = 32,
    n_cap: int = 1 << 16,
    backend: str = "poisson",
    metric: str = "l2",
    growth_cap: float = 8.0,
    ext_cap: Optional[int] = None,
    adaptive: bool = True,
    use_kernel: bool = False,
    gate_gather: bool = True,
) -> FusedResult:
    """The unsharded (data_shards == 1) closed loop (SS7 phase C/D).

    A thin closed-loop wrapper over :func:`fused_step`'s body: init the
    carry, tick until every lane is done/failed/out of ticks, project the
    result.  Every per-lane computation (fit, predict, window, bootstrap) is
    lane-separable, so a lane's trajectory is bit-identical to running it
    alone with the same keys; lanes that converge early are frozen
    (predicated updates) while the loop serves the stragglers.  The ESTIMATE
    width bucket is shared -- the max watermark over still-active lanes --
    which is statistically invisible because the counter-PRNG weight draws
    do not depend on the bucket width.

    ``sample_keys``: ``None`` derives one slot->row binding per lane from
    ``keys``; shape ``(2,)`` shares ONE binding (and slot table) across all
    lanes -- the server's shared-prefix epoch policy; shape ``(q, 2)`` pins
    one per lane.

    ``est_name=None`` makes lanes heterogeneous: lane i runs the moment-
    family estimator ``est_fids[i]`` (estimators.moment_family_index).

    ``backend="poisson"`` (default) uses the width-invariant counter-PRNG
    Poisson weights (kernel-backed for moment estimators when
    ``use_kernel``); other backends fall back to
    :func:`~.bootstrap.estimate_error` per lane, whose jax.random draws are
    width-dependent -- pair them with ``adaptive=False`` when exact
    bucket-boundary invariance matters.
    """
    m = offsets.shape[0] - 1
    ext_cap = resolve_ext_cap(n_cap, n_max, ext_cap)
    params = make_lane_params(
        offsets, scale, keys, epsilons, deltas, sample_keys, est_fids,
        n_cap=n_cap, warm_n0=warm_n0, warm_beta=warm_beta)
    p_dim = (get_estimator(est_name).out_dim(values.shape[1])
             if est_name is not None else 1)
    state0 = init_lane_state(
        keys, m, n_cap=n_cap, c_dim=values.shape[1], p_dim=p_dim,
        n_min=n_min, max_iters=max_iters, dtype=values.dtype)
    spec = dict(
        est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
        max_iters=max_iters, n_cap=n_cap, backend=backend, metric=metric,
        growth_cap=growth_cap, ext_cap=ext_cap, adaptive=adaptive,
        use_kernel=use_kernel, gate_gather=gate_gather)
    cols = as_columns(values)     # once per call, outside the loop

    state = jax.lax.while_loop(
        lambda st: jnp.any(lane_active(st, max_iters)),
        lambda st: _step_body(cols, offsets, st, params, **spec),
        state0)
    return lanes_result(state)


def fused_l2miss(
    values: Array,        # (N, c) group-sorted rows
    offsets: Array,       # (m + 1,)
    scale: Array,         # (m,)
    key: Array,
    epsilon: Array,
    delta,
    sample_key: Optional[Array] = None,
    warm_n0: Optional[Array] = None,      # (m,) warm-start prediction
    warm_beta: Optional[Array] = None,    # (m+1,) cached coefficients
    **static_kwargs,
) -> FusedResult:
    """Single-query entry point: the q=1 lane configuration.

    Same contract as the pre-phase-C fused loop; accepts the same static
    kwargs as :func:`fused_l2miss_lanes` (notably ``adaptive`` -- width
    bucketing on by default -- and ``use_kernel``).
    """
    res = fused_l2miss_lanes(
        values, offsets,
        jnp.asarray(scale)[None],
        jnp.asarray(key)[None],
        jnp.asarray(epsilon, jnp.float32)[None],
        jnp.asarray(delta, jnp.float32)[None],
        None if sample_key is None else jnp.asarray(sample_key),
        warm_n0=None if warm_n0 is None
        else jnp.asarray(warm_n0, jnp.int32)[None],
        warm_beta=None if warm_beta is None
        else jnp.asarray(warm_beta, jnp.float32)[None],
        **static_kwargs)
    return FusedResult(*(x[0] for x in res))


@partial(jax.jit, static_argnames=_STEP_STATICS + ("seg_cap",))
def _fused_grouped_closed(
    values: Array,
    offsets: Array,       # (G + 1,) REAL group offsets (host-visible)
    scale: Array,         # (G,)
    keys: Array,          # (G, 2)
    epsilons: Array,      # (G,)
    deltas: Array,        # (G,)
    sample_key: Array,    # (2,)
    est_fids: Array,      # (G,)
    *,
    est_name: Optional[str],
    B: int,
    n_min: int,
    n_max: int,
    l: int,
    tau: float,
    max_iters: int,
    n_cap: int,
    backend: str,
    metric: str,
    growth_cap: float,
    ext_cap: int,
    adaptive: bool,
    use_kernel: bool,
    gate_gather: bool,
    seg_cap: int,
) -> FusedResult:
    """Closed-loop driver over the grouped-block step (phase I)."""
    params = make_group_lane_params(
        offsets, scale, keys, epsilons, deltas, sample_key, est_fids,
        n_cap=n_cap)
    p_dim = (get_estimator(est_name).out_dim(values.shape[1])
             if est_name is not None else 1)
    state0 = init_lane_state(
        keys, 1, n_cap=n_cap, c_dim=values.shape[1], p_dim=p_dim,
        n_min=n_min, max_iters=max_iters, dtype=values.dtype)
    step_offsets = jnp.asarray([0, values.shape[0]], jnp.int32)
    spec = dict(
        est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
        max_iters=max_iters, n_cap=n_cap, backend=backend, metric=metric,
        growth_cap=growth_cap, ext_cap=ext_cap, adaptive=adaptive,
        use_kernel=use_kernel, gate_gather=gate_gather, seg_cap=seg_cap)
    cols = as_columns(values)     # once per call, outside the loop
    state = jax.lax.while_loop(
        lambda st: jnp.any(lane_active(st, max_iters)),
        lambda st: _step_body(cols, step_offsets, st, params, **spec),
        state0)
    return lanes_result(state)


def fused_grouped(
    values: Array,        # (N, c) group-sorted rows
    offsets: Array,       # (G + 1,)
    scale: Array,         # (G,) per-group scale (population_scale_row)
    key: Array,           # the grouped QUERY key
    epsilon,              # scalar | (G,) per-group bound
    delta,                # scalar | (G,)
    sample_key: Optional[Array] = None,
    est_fids: Optional[Array] = None,
    *,
    est_name: Optional[str] = "avg",
    B: int = 500,
    n_min: int = 100,
    n_max: int = 200,
    l: int = 10,
    tau: float = 1e-3,
    max_iters: int = 32,
    n_cap: int = 1 << 16,
    metric: str = "l2",
    growth_cap: float = 8.0,
    ext_cap: Optional[int] = None,
    use_kernel: bool = False,
) -> FusedResult:
    """GROUP BY entry point (phase I): one shared-scan block of G lanes.

    Admits a grouped query as a BLOCK of ``G = len(offsets) - 1`` per-group
    MISS lanes -- lane g's bootstrap key is ``fold_in(key, g)`` and its
    slot table is the stratified store's stratum g -- and runs the block to
    convergence with the segment-aggregated step: every tick pays one
    packed gather plus one segment moment pass over the union of active
    windows, not G independent ESTIMATE dispatches.  Each group converges,
    extends, and parks independently under its own ``(epsilon, delta)``
    row, so the result is G verdicts equivalent to G solo
    :func:`fused_l2miss` runs on the group slices (same keys, same
    ``stratum_key`` sample bindings) within the documented f32-summation
    tolerance.

    Returns a :class:`FusedResult` with the GROUP axis leading and the
    degenerate ``m = 1`` axis squeezed: ``n (G,)``, ``error (G,)``,
    ``theta (G, p)``, ``success (G,)``, ``profile_n (G, max_iters)`` --
    group g's row is its lane's whole trajectory.
    """
    offsets = jnp.asarray(offsets, jnp.int32)
    G = int(offsets.shape[0]) - 1
    keys = jax.vmap(lambda g: jax.random.fold_in(key, g))(jnp.arange(G))
    epsilons = jnp.broadcast_to(
        jnp.asarray(epsilon, jnp.float32), (G,))
    deltas = jnp.broadcast_to(jnp.asarray(delta, jnp.float32), (G,))
    if sample_key is None:
        sample_key = key
    if est_fids is None:
        est_fids = jnp.zeros((G,), jnp.int32)
    seg_cap = grouped_seg_cap(np.asarray(offsets), n_cap)
    res = _fused_grouped_closed(
        values, offsets, jnp.asarray(scale, jnp.float32), keys, epsilons,
        deltas, jnp.asarray(sample_key), jnp.asarray(est_fids, jnp.int32),
        est_name=est_name, B=B, n_min=n_min, n_max=n_max, l=l, tau=tau,
        max_iters=max_iters, n_cap=n_cap, backend="poisson", metric=metric,
        growth_cap=growth_cap,
        ext_cap=resolve_ext_cap(n_cap, n_max, ext_cap), adaptive=True,
        use_kernel=use_kernel, gate_gather=True, seg_cap=seg_cap)
    return FusedResult(
        n=res.n[:, 0], error=res.error, theta=res.theta[:, 0],
        iterations=res.iterations, success=res.success, failed=res.failed,
        beta=res.beta, r2=res.r2, profile_n=res.profile_n[:, :, 0],
        profile_e=res.profile_e, rows_sampled=res.rows_sampled)
