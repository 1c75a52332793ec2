"""Bootstrap error estimation (paper SS4.2), vectorized for TPU.

Two interchangeable resampling backends:

  * ``poisson``      -- replicate weights w_b = mask * Poisson(1); every
                        replicate is a weighted reduction (vmap over B).
                        TPU-native: no gathers (DESIGN.md SS3).  Default.
  * ``multinomial``  -- classic with-replacement index resampling (gathers);
                        kept as the statistical reference / CPU oracle.

The ESTIMATE subroutine of MISS: given a stratified sample and an estimator,
return the 1-delta quantile of the bootstrap distribution of the *joint*
error metric across groups (groups are resampled independently, matching
stratified sampling independence).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .estimators import Estimator, moment_family
from ..kernels import prng

Array = jax.Array


# Poisson(1) CDF ladder: P(X <= k) for k = 0..9.  Inverse-CDF sampling via
# 10 fused comparisons is ~30x cheaper than jax.random.poisson's rejection
# sampler and is exactly the scheme the Pallas kernel uses on TPU, so the
# jnp path and the kernel share a distribution (truncation mass < 1e-10).
_POISSON1_CDF = (
    0.36787944117144233, 0.7357588823428847, 0.9196986029286058,
    0.9810118431238462, 0.9963401531726563, 0.9994058151824183,
    0.9999167588507119, 0.9999897508033253, 0.9999988747974149,
    0.9999998885745217,
)


def poisson_weights(key: Array, B: int, n: int, dtype=jnp.float32) -> Array:
    """(B, n) iid Poisson(1) resample-count weights (inverse-CDF ladder)."""
    u = jax.random.uniform(key, (B, n))
    w = jnp.zeros((B, n), dtype)
    for c in _POISSON1_CDF:
        w = w + (u >= c).astype(dtype)
    return w


def multinomial_weights(key: Array, B: int, mask: Array, dtype=jnp.float32) -> Array:
    """(B, n) exact multinomial resample counts over the valid rows.

    Inverse-CDF sampling (searchsorted over the cumulative mask) -- O(B n
    log n); jax.random.categorical would materialize the O(B n^2) gumbel
    tensor.  Gather/scatter-bound; reference backend only.
    """
    n = mask.shape[0]
    w = mask.astype(jnp.float32)
    cdf = jnp.cumsum(w) / jnp.maximum(jnp.sum(w), 1e-9)
    u = jax.random.uniform(key, (B, n))
    idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"), 0, n - 1)
    # Replicates must have exactly n_valid draws: drop the padding draws.
    n_valid = jnp.sum(mask)
    keep = jnp.broadcast_to(jnp.arange(n)[None, :] < n_valid, (B, n))
    counts = jax.vmap(
        lambda ix, kp: jnp.zeros((n,), dtype).at[ix].add(kp.astype(dtype))
    )(idx, keep)
    return counts * mask[None, :]


def _weights(est, x, mask, key, B, backend):
    if backend == "poisson":
        w = poisson_weights(key, B, x.shape[0]) * mask[None, :]
        # Guard against an all-zero Poisson draw on tiny samples: fall back to
        # the original mask (identity replicate) when a row of weights is 0.
        dead = jnp.sum(w, axis=1, keepdims=True) <= 0
        w = jnp.where(dead, mask[None, :], w)
        return w
    if backend == "multinomial":
        return multinomial_weights(key, B, mask)
    raise ValueError(f"unknown bootstrap backend {backend!r}")


# Estimators whose CLT standard error NormalMiss can compute in closed form.
_NORMAL_OK = ("avg", "proportion", "sum", "count", "var", "std")


def normal_replicates(est: Estimator, x: Array, mask: Array, key: Array,
                      B: int) -> Array:
    """NormalMiss backend (paper SS6.2): CLT-based Gaussian replicates
    theta* ~ N(theta_hat, avar/n) -- no resampling, B cheap draws.  Only
    valid where asymptotic normality holds (BLK's assumption set)."""
    if est.name not in _NORMAL_OK:
        raise ValueError(f"normal backend unsupported for {est.name}")
    v = (x[:, 0] if x.ndim == 2 else x).astype(jnp.float32)
    w = mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(w), 1.0)
    mean = jnp.sum(w * v) / n
    var = jnp.sum(w * (v - mean) ** 2) / n
    if est.name == "var":
        mu4 = jnp.sum(w * (v - mean) ** 4) / n
        theta, avar = var, jnp.maximum(mu4 - var**2, 1e-12)
    elif est.name == "std":
        sd = jnp.sqrt(jnp.maximum(var, 1e-12))
        mu4 = jnp.sum(w * (v - mean) ** 4) / n
        theta, avar = sd, jnp.maximum(mu4 - var**2, 1e-12) / (4 * var)
    else:
        theta, avar = mean, var
    se = jnp.sqrt(avar / n)
    z = jax.random.normal(key, (B, 1))
    return theta + se * z


def replicates(
    est: Estimator,
    x: Array,
    mask: Array,
    key: Array,
    B: int,
    backend: str = "poisson",
) -> Array:
    """(B, p) bootstrap replicates of f on one group's sample.

    Moment estimators take the matmul fast path: all B replicates are one
    (B, n) @ (n, 3) product over [1, x, x^2] -- the same formulation the
    Pallas kernel implements on TPU (kernels/poisson_bootstrap)."""
    if backend == "normal":
        return normal_replicates(est, x, mask, key, B)
    w = _weights(est, x, mask, key, B, backend)
    if est.moments_finish is not None:
        v = x[:, 0] if x.ndim == 2 else x
        feats = jnp.stack([jnp.ones_like(v), v, v * v], axis=1)  # (n, 3)
        M = jnp.matmul(w, feats, precision="highest")            # (B, 3)
        return est.moments_finish(M)
    aux = est.prepare(x)
    return jax.vmap(lambda wb: est.apply(aux, wb))(w)


@partial(jax.jit, static_argnames=("est", "B", "backend", "metric"))
def estimate_error(
    est: Estimator,
    sample: Array,   # (m, n_cap, c) stratified sample
    mask: Array,     # (m, n_cap)
    scale: Array,    # (m,) per-group |D|_i scale (1.0 for consistent f)
    key: Array,
    delta: float,
    B: int = 500,
    backend: str = "poisson",
    metric: str = "l2",
) -> Tuple[Array, Array]:
    """ESTIMATE: (e, theta_hat) for the joint metric across m groups.

    e is the (1 - delta) quantile of d(theta*_b, theta_hat) where every group
    is independently resampled in replicate b.  metric in {l2, linf, l1, per
    -group-max aka linf}.  Per-group multi-output estimators (regressions)
    contribute their own L2 coefficient error before the cross-group combine.
    """
    m = sample.shape[0]
    keys = jax.random.split(key, m)

    def per_group(xg, mg, kg):
        aux = est.prepare(xg)
        theta = est.apply(aux, mg)
        reps = replicates(est, xg, mg, kg, B, backend)
        return theta, reps

    theta_hat, reps = jax.vmap(per_group)(sample, mask, keys)  # (m,p),(m,B,p)
    # Per-group scalar error per replicate: L2 over the estimator outputs.
    dev = reps - theta_hat[:, None, :]                # (m, B, p)
    per_group_err = jnp.sqrt(jnp.sum(dev**2, axis=-1))  # (m, B)
    per_group_err = per_group_err * scale[:, None]
    joint = _joint_metric(per_group_err, metric, axis=0)  # (B,)
    e = jnp.quantile(joint, 1.0 - delta)
    return e, theta_hat * scale[:, None]


def _joint_metric(per_group_err: Array, metric: str, axis: int = 0) -> Array:
    """Combine per-group scalar errors into the joint metric along ``axis``."""
    if metric == "l2":
        return jnp.sqrt(jnp.sum(per_group_err**2, axis=axis))
    if metric == "linf":
        return jnp.max(per_group_err, axis=axis)
    if metric == "l1":
        return jnp.sum(per_group_err, axis=axis)
    raise ValueError(f"unknown metric {metric!r}")  # pragma: no cover


def lane_moment_sums(v, mf, seeds, B, *, use_kernel=False, interpret=None,
                     lane_active=None):
    """RAW (unguarded) replicate moment sums shared by every moments-fast-path
    estimator -- and, per shard segment, by the sharded fused step.

    ``(M (q, m, B, 3), M_plain (q, m, 3))`` where row b of M is
    ``[sum w, sum w x, sum w x^2]`` under the counter-PRNG Poisson weights
    and M_plain is the unweighted (mask-only) sums.  Heterogeneous lanes
    (``estimate_error_lanes_het``) and homogeneous lanes
    (``estimate_error_lanes``) both come through here, so a lane's replicate
    sums are identical whichever entry point served it.

    Sums are returned RAW so they can be summed across shard segments (the
    Poisson bootstrap composes over row shards, DESIGN.md SS3/phase G) --
    the dead-replicate guard only makes sense on the COMBINED sums and lives
    in :func:`guard_dead_replicates` / :func:`finish_lanes_moments`.

    ``lane_active`` (optional, (q,) bool): lanes marked inactive SKIP the
    weight generation + contraction entirely and report zero sums.  Callers
    may only pass it when they discard inactive lanes' outputs (the fused
    loop's frozen-lane predication) -- it changes what those lanes COST,
    never what active lanes compute: the jnp path walks lanes with
    ``lax.map``, where a ``lax.cond`` is a real branch, not the
    execute-both of vmapped control flow.  This is what keeps a lane pool's
    straggler tail (one live lane, q-1 parked) from paying q lanes of
    bootstrap compute per tick.  The kernel path gets the same gating at
    grid level (DESIGN.md SS7 phase E): the flag is broadcast over the
    lane's groups and each inactive group's tiles early-exit under
    ``pl.when`` -- no weight tile, no MXU contraction.  Both paths report
    identical zeros for inactive lanes, so kernel-vs-jnp parity holds for
    any flag pattern.
    """
    q, m, w = mf.shape
    feats = jnp.stack([mf, mf * v, mf * v * v], axis=-1)       # (q, m, w, 3)
    M_plain = jnp.sum(feats, axis=2)                           # (q, m, 3)
    if use_kernel:
        from ..kernels.poisson_bootstrap import ops as pb_ops
        act = (None if lane_active is None
               else jnp.broadcast_to(lane_active[:, None], (q, m)))
        M = pb_ops.bootstrap_moments_masked(
            v, mf, seeds, B, lane_active=act, interpret=interpret)[..., :3]
    else:
        rows = jnp.arange(w, dtype=jnp.uint32)
        cols = jnp.arange(B, dtype=jnp.uint32)

        # One lane at a time (lax.map): the transient (m, w, B) weight
        # tensor is the peak the phase-B per-query loop already paid;
        # materializing all q lanes at once would scale it by the lane
        # count (~2.4 GB at service defaults with 16 lanes in the top
        # bucket).  The kernel path never materializes weights at all.
        def lane_M(feats_l, seeds_l):                          # (m,w,3), (m,)
            W = prng.poisson1_weights_at(
                seeds_l[:, None, None].astype(jnp.uint32),
                rows[:, None], cols[None, :])                  # (m, w, B)
            return jnp.einsum("mnb,mnp->mbp", W, feats_l,
                              precision="highest")

        if lane_active is None:
            M = jax.lax.map(lambda a: lane_M(*a), (feats, seeds))
        else:
            M = jax.lax.map(
                lambda a: jax.lax.cond(
                    a[2], lambda t: lane_M(t[0], t[1]),
                    lambda t: jnp.zeros((m, B, 3), jnp.float32), a[:2]),
                (feats, seeds, lane_active))                   # (q, m, B, 3)
    return M, M_plain


def windowed_lane_moment_sums(vals, lo, hi, seeds, B, widths, *,
                              lane_active, chunk=4):
    """RAW replicate moment sums over per-lane WINDOWS, rungs per CHUNK.

    The sharded fused step's ESTIMATE (DESIGN.md phase G): ``vals (q, m,
    cap)`` is one shard segment's value column, ``lo``/``hi (q, m)`` each
    (lane, group)'s live window in segment-local slots, ``widths`` a static
    ascending rung ladder topped by ``cap``.  Differences from
    :func:`lane_moment_sums` that pay on a segment:

    - WINDOWED, not prefix: a lane gathers ``[lo, lo+w)`` at its own rung
      ``w`` -- the init design parks windows several multiples of n_max up
      the buffer, and prefix semantics would price every lane by its high
      watermark instead of its window width (~n/S local rows).
    - Rungs per CHUNK of ``chunk`` lanes, not one global rung: a wide lane
      (a straggler mid-jump) drags only its chunk-mates onto its rung, and
      an all-parked chunk skips weights and contraction entirely.  Chunks
      balance two fixed costs a big pool multiplies: per-lane ``lax.map``
      iteration overhead (why not per-lane rungs) and the transient
      ``(chunk, m, w, B)`` weight tensor (why not one vectorized shot --
      though windowed rungs are what make even chunked tensors small).
      Inactive lanes inside a live chunk contribute exact zeros via the
      mask, matching the skipped-chunk zeros bitwise.

    Weights hash on ABSOLUTE segment-local slot positions: a slot's Poisson
    replicate stream is a pure function of (lane, group, shard, slot), so
    where the window lands in the gathered slice never reweights a row.
    Sums are RAW for the same reason as :func:`lane_moment_sums`: the
    cross-shard combine (psum / sequential fold) and the dead-replicate
    guard run on the combined result.
    """
    q, m, cap = vals.shape
    if widths[-1] != cap:
        raise ValueError(f"width ladder {widths} must top out at cap={cap}")
    c = max(1, min(int(chunk), q))
    qp = -(-q // c) * c
    if qp != q:
        def pad(a, fill):
            tail = jnp.full((qp - q,) + a.shape[1:], fill, a.dtype)
            return jnp.concatenate([a, tail], axis=0)
        vals, lo, hi = pad(vals, 0), pad(lo, 0), pad(hi, 0)
        seeds, lane_active = pad(seeds, 0), pad(lane_active, False)
    w_arr = jnp.asarray(widths[:-1], jnp.int32)
    cols = jnp.arange(B, dtype=jnp.uint32)

    def chunk_sums(args):
        vals_c, lo_c, hi_c, seeds_c, act_c = args              # (c, m, ...)
        actf = act_c.astype(jnp.float32)[:, None, None]
        need = jnp.max(jnp.where(act_c[:, None], hi_c - lo_c, 0))
        b = jnp.sum(need > w_arr).astype(jnp.int32)

        def mk(width):
            def branch(_):
                lo_w = jnp.clip(lo_c, 0, cap - width)          # (c, m)
                pos = (lo_w[:, :, None] +
                       jnp.arange(width, dtype=jnp.int32))     # (c, m, w)
                vv = jnp.take_along_axis(
                    vals_c, pos, axis=2).astype(jnp.float32)
                mf = ((pos >= lo_c[..., None]) &
                      (pos < hi_c[..., None])).astype(jnp.float32) * actf
                feats = jnp.stack(
                    [mf, mf * vv, mf * vv * vv], axis=-1)      # (c, m, w, 3)
                W = prng.poisson1_weights_at(
                    seeds_c[:, :, None, None].astype(jnp.uint32),
                    pos[..., None].astype(jnp.uint32),
                    cols[None, None, None, :])                 # (c, m, w, B)
                return (jnp.einsum("cmnb,cmnp->cmbp", W, feats,
                                   precision="highest"),
                        jnp.sum(feats, axis=2))
            return branch

        return jax.lax.cond(
            jnp.any(act_c),
            lambda _: jax.lax.switch(b, [mk(w) for w in widths], 0),
            lambda _: (jnp.zeros((c, m, B, 3), jnp.float32),
                       jnp.zeros((c, m, 3), jnp.float32)),
            0)

    grp = lambda a: a.reshape((qp // c, c) + a.shape[1:])
    M, M_plain = jax.lax.map(
        chunk_sums, (grp(vals), grp(lo), grp(hi), grp(seeds),
                     grp(lane_active)))
    return (M.reshape(qp, m, B, 3)[:q],
            M_plain.reshape(qp, m, 3)[:q])


def segment_moment_sums(x, gid, slot, valid, seeds, q, B, *,
                        use_kernel=False, interpret=None, tn=2048):
    """RAW replicate moment sums over one PACKED stream of lane windows.

    The grouped-block ESTIMATE (DESIGN.md phase I): ``x (L,)`` are the
    gathered values of ALL active lanes' windows concatenated, ``gid (L,)``
    the owning lane, ``slot (L,)`` each element's ABSOLUTE buffer slot,
    ``valid (L,)`` stream validity (padding + frozen lanes contribute
    nothing), ``seeds (q,)`` the per-lane tick seeds.  Returns ``(M (q, B,
    3), M_plain (q, 3))`` with weight (j, b) = ``poisson1(hash3(seeds[gid_j],
    slot_j, b))`` -- the SAME draw :func:`lane_moment_sums` makes for that
    (lane, slot, replicate), so a block lane's statistics match its solo
    run; only f32 summation order differs (segment adds vs per-lane dot),
    which is why grouped parity is asserted at the sharded pool's tolerance
    rather than bitwise.

    Cost tracks the stream length: ONE weight generation + ONE segment
    reduction for all q lanes, instead of q per-lane contractions each
    priced at the global width bucket.  With ``use_kernel`` the weights are
    generated in VMEM by ``kernels/segment_agg.segment_bootstrap_moments``
    (bit-identical to its jnp oracle); the jnp path chunks the stream so
    the transient (tn, B, 3) contribution tensor stays bounded.
    """
    L = x.shape[0]
    mf = valid.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    gid = jnp.clip(gid.astype(jnp.int32), 0, q - 1)
    feats = jnp.stack([mf, mf * xf, mf * xf * xf], axis=-1)    # (L, 3)
    M_plain = jax.ops.segment_sum(feats, gid, num_segments=q)  # (q, 3)
    if use_kernel:
        from ..kernels.segment_agg import ops as seg_ops
        M = seg_ops.segment_bootstrap_moments(
            gid, slot.astype(jnp.int32), xf, mf, seeds[gid], q, B,
            interpret=interpret)
        return M, M_plain
    chunks = -(-L // tn)
    Lp = chunks * tn
    if Lp != L:
        padc = Lp - L
        feats = jnp.pad(feats, ((0, padc), (0, 0)))
        gid = jnp.pad(gid, (0, padc))
        slot = jnp.pad(slot, (0, padc))
    seed_flat = seeds[gid].astype(jnp.uint32)                  # (Lp,)
    cols = jnp.arange(B, dtype=jnp.uint32)

    def body(i, M):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * tn, tn)
        W = prng.poisson1_weights_at(
            sl(seed_flat)[:, None], sl(slot)[:, None].astype(jnp.uint32),
            cols[None, :])                                     # (tn, B)
        C = W[:, :, None] * sl(feats)[:, None, :]              # (tn, B, 3)
        return M + jax.ops.segment_sum(C, sl(gid), num_segments=q)

    M = jax.lax.fori_loop(
        0, chunks, body, jnp.zeros((q, B, 3), jnp.float32))
    return M, M_plain


def guard_dead_replicates(M: Array, M_plain: Array) -> Array:
    """Substitute the plain sample for dead replicates (``sum w == 0``).

    Applied to COMBINED moment sums: under sharding a replicate is dead only
    if its weights vanished on every shard, so the guard must run after the
    cross-shard psum, never per segment.
    """
    dead = M[..., 0:1] <= 0
    return jnp.where(dead, M_plain[:, :, None, :], M)


def _lane_moment_sums(v, mf, seeds, B, use_kernel, interpret,
                      lane_active=None):
    """Guarded moment sums (compat shim: raw sums + dead-replicate guard)."""
    M, M_plain = lane_moment_sums(v, mf, seeds, B, use_kernel=use_kernel,
                                  interpret=interpret, lane_active=lane_active)
    return guard_dead_replicates(M, M_plain), M_plain


def finish_lanes_moments(
    M: Array,        # (q, m, B, 3) RAW combined replicate moment sums
    M_plain: Array,  # (q, m, 3) combined plain (mask-only) sums
    scale: Array,    # (q, m)
    deltas: Array,   # (q,)
    est: "Estimator | None" = None,
    est_fids: Optional[Array] = None,
    metric: str = "l2",
) -> Tuple[Array, Array]:
    """(e, theta) from combined replicate moment sums -- the post-psum
    epilogue of the moments fast path.

    Exactly the op sequence the moments branches of
    :func:`estimate_error_lanes` (pass ``est``) and
    :func:`estimate_error_lanes_het` (pass ``est_fids``) run after their
    moment pass, factored out so the sharded fused step can run it on
    psum-combined sums: guard dead replicates, finish to replicates/theta,
    deviations -> per-group errors -> joint metric -> per-lane quantile.
    """
    M = guard_dead_replicates(M, M_plain)
    if est is not None:
        reps = est.moments_finish(M)                           # (q, m, B, 1)
        theta = est.moments_finish(M_plain[:, :, None, :])[:, :, 0, :]
    else:
        fam = moment_family()
        branches = tuple(e.moments_finish for e in fam)

        def finish_lane(fid, M_l, Mp_l):
            # Under vmap the switch lowers to compute-all-and-select; the
            # finish epilogues are elementwise on (m, B, 3) sums, so that is
            # noise next to the moment matmul -- and select keeps the chosen
            # branch's values bitwise intact.
            reps_l = jax.lax.switch(fid, branches, M_l)        # (m, B, 1)
            th_l = jax.lax.switch(fid, branches, Mp_l[:, None, :])[:, 0, :]
            return reps_l, th_l

        reps, theta = jax.vmap(finish_lane)(
            est_fids.astype(jnp.int32), M, M_plain)
    dev = reps - theta[:, :, None, :]                          # (q, m, B, p)
    per_group_err = jnp.sqrt(jnp.sum(dev**2, axis=-1)) * scale[..., None]
    joint = _joint_metric(per_group_err, metric, axis=1)       # (q, B)
    e = jax.vmap(lambda j, d: jnp.quantile(j, 1.0 - d))(joint, deltas)
    return e, theta * scale[..., None]


def estimate_error_lanes(
    est: Estimator,
    sample: Array,   # (q, m, w, c) width-bucketed slice of the carried buffer
    mask: Array,     # (q, m, w)
    seeds: Array,    # (q, m) uint32 counter-PRNG seeds (one stream per group)
    scale: Array,    # (q, m)
    deltas: Array,   # (q,)
    B: int = 500,
    metric: str = "l2",
    use_kernel: bool = False,
    interpret: "bool | None" = None,
    lane_active: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Lane-batched ESTIMATE on counter-PRNG Poisson weights (SS7 phase C).

    The fused loop's bucketed bootstrap: ``q`` independent query lanes over
    the same grouping layout, each estimated on a width-``w`` slice of its
    carried sample.  Weight entry (j, b) of group (lane, i) is
    ``poisson1(hash3(seeds[lane, i], j, b))`` with j the ABSOLUTE buffer
    slot, so the draws -- and hence (e, theta) -- are invariant to the
    bucket width ``w``: widening the slice only appends zero-mask rows whose
    weights multiply zeroed features.  This is what makes ``lax.switch``
    over width buckets safe: crossing a bucket boundary changes compute
    width, never the statistics.

    Moment estimators contract all B replicates as one masked-features
    matmul -- the formulation ``kernels/poisson_bootstrap`` implements on
    TPU; with ``use_kernel`` the (w, B) weight matrix is generated in VMEM
    by the kernel and never materialized in HBM.  Both paths consume the
    SAME counter stream, so kernel vs jnp agree bit-comparably (interpret
    mode) rather than only statistically.
    """
    q, m, w = mask.shape
    v = (sample[..., 0] if sample.ndim == 4 else sample).astype(jnp.float32)
    mf = mask.astype(jnp.float32)
    if est.moments_finish is not None:
        M, M_plain = lane_moment_sums(v, mf, seeds, B, use_kernel=use_kernel,
                                      interpret=interpret,
                                      lane_active=lane_active)
        return finish_lanes_moments(M, M_plain, scale, deltas, est=est,
                                    metric=metric)
    else:
        rows = jnp.arange(w, dtype=jnp.uint32)
        cols = jnp.arange(B, dtype=jnp.uint32)

        def one_group(xg, mg, sg):
            aux = est.prepare(xg)
            Wg = prng.poisson1_weights_at(
                sg, rows[:, None], cols[None, :]) * mg[:, None]  # (w, B)
            dead = jnp.sum(Wg, axis=0, keepdims=True) <= 0
            Wg = jnp.where(dead, mg[:, None], Wg)
            reps = jax.vmap(lambda wb: est.apply(aux, wb))(Wg.T)  # (B, p)
            return est.apply(aux, mg), reps

        theta, reps = jax.vmap(jax.vmap(one_group))(sample, mf, seeds)
    dev = reps - theta[:, :, None, :]                          # (q, m, B, p)
    per_group_err = jnp.sqrt(jnp.sum(dev**2, axis=-1)) * scale[..., None]
    joint = _joint_metric(per_group_err, metric, axis=1)       # (q, B)
    e = jax.vmap(lambda j, d: jnp.quantile(j, 1.0 - d))(joint, deltas)
    return e, theta * scale[..., None]


def estimate_error_lanes_het(
    sample: Array,   # (q, m, w, c) width-bucketed slice of the carried buffer
    mask: Array,     # (q, m, w)
    seeds: Array,    # (q, m) uint32 counter-PRNG seeds
    est_fids: Array, # (q,) int32 moment-FAMILY indices (estimators.moment_family)
    scale: Array,    # (q, m)
    deltas: Array,   # (q,)
    B: int = 500,
    metric: str = "l2",
    use_kernel: bool = False,
    interpret: "bool | None" = None,
    lane_active: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Heterogeneous-lane ESTIMATE: one pool, a different estimator per lane.

    Every moments-fast-path estimator (avg/proportion/var/std/sum/count)
    shares the SAME replicate moment sums -- the masked counter-PRNG weight
    matmul of :func:`_lane_moment_sums` -- and differs only in the cheap
    ``moments_finish`` epilogue.  So mixed-func lanes cost one moment pass
    (kernel-backed under ``use_kernel``) plus a per-lane ``lax.switch`` over
    the family's finish branches.  Because the selected branch applies the
    identical function to identical sums, a lane's (e, theta) here equals
    the homogeneous :func:`estimate_error_lanes` for its estimator -- which
    is what lets a heterogeneous lane pool answer each lane bit-comparably
    to a solo single-func run (serve/lane_pool.py).

    ``est_fids`` are FAMILY indices (branch positions from
    ``estimators.moment_family_index``), not global registry ids.  SUM/COUNT
    lanes carry their population scale in their ``scale`` row (the paper
    SS2.2.1 transformation), exactly as the homogeneous path does.
    """
    v = (sample[..., 0] if sample.ndim == 4 else sample).astype(jnp.float32)
    mf = mask.astype(jnp.float32)
    M, M_plain = lane_moment_sums(v, mf, seeds, B, use_kernel=use_kernel,
                                  interpret=interpret, lane_active=lane_active)
    return finish_lanes_moments(M, M_plain, scale, deltas, est_fids=est_fids,
                                metric=metric)


def per_group_errors(
    est: Estimator,
    sample: Array,
    mask: Array,
    scale: Array,
    key: Array,
    delta: float,
    B: int = 500,
    backend: str = "poisson",
) -> Array:
    """(m,) per-group (1-delta)-quantile errors (used by BLK-style baselines)."""
    m = sample.shape[0]
    keys = jax.random.split(key, m)

    def per_group(xg, mg, kg):
        aux = est.prepare(xg)
        theta = est.apply(aux, mg)
        reps = replicates(est, xg, mg, kg, B, backend)
        err = jnp.sqrt(jnp.sum((reps - theta[None, :]) ** 2, axis=-1))
        return jnp.quantile(err, 1.0 - delta)

    return jax.vmap(per_group)(sample, mask, keys) * scale
