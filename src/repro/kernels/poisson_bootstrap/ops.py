"""jit'd wrappers around the poisson_bootstrap kernel.

``bootstrap_moments``         one group  -> (B, 5) replicate moment sums
``bootstrap_moments_masked``  masked variable-width entry point: arbitrary
                              leading dims of (lane, group) samples, explicit
                              uint32 counter seeds -- the fused-loop ESTIMATE
                              path (DESIGN.md SS7 phase C).  Weight draws are
                              a pure function of (seed, row, replicate), so
                              the result is invariant to the padded width:
                              slicing the sample to a wider bucket with zero
                              mask beyond the watermark changes nothing.
                              ``lane_active`` (phase E) gates whole groups at
                              grid level: inactive groups skip weight
                              generation + the MXU contraction and report
                              zero sums; active groups are bit-equal to an
                              all-active call.
``estimate_error_moments``    drop-in replacement for
                              core.bootstrap.estimate_error for the moment
                              estimators (avg/var/std/sum/count/proportion):
                              same (e, theta_hat) contract, bootstrap
                              replicates computed by the Pallas kernel.

With ``interpret=None`` the kernel compiles to Mosaic on a TPU and runs in
interpret mode elsewhere (``kernels.interpret_default``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.bootstrap import _joint_metric
from ...core.estimators import get as get_estimator
from .. import interpret_default
from . import kernel as K


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def build_feats(x: jax.Array, mask: jax.Array, n_pad: int) -> jax.Array:
    """(P, n_pad) masked moment features [m, mx, mx^2, mx^3, mx^4, 0, 0, 0]."""
    n = x.shape[0]
    x = jnp.pad(x.astype(jnp.float32), (0, n_pad - n))
    m = jnp.pad(mask.astype(jnp.float32), (0, n_pad - n))
    x2 = x * x
    rows = [m, m * x, m * x2, m * x2 * x, m * x2 * x2]
    zeros = jnp.zeros_like(x)
    rows += [zeros] * (K.P - len(rows))
    return jnp.stack(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("B", "tb", "tn", "interpret"))
def bootstrap_moments(
    x: jax.Array,          # (n,) sample values
    mask: jax.Array,       # (n,) validity
    seed: jax.Array,       # scalar uint32/int32
    B: int = 500,
    *,
    tb: int = 256,
    tn: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, 5) replicate moment sums [sum w, sum wx, ..., sum wx^4]."""
    if interpret is None:
        interpret = interpret_default()
    n_pad = _round_up(x.shape[0], tn)
    B_pad = _round_up(B, tb)
    feats = build_feats(x, mask, n_pad)
    M = K.poisson_bootstrap_moments(
        feats, jnp.asarray([seed], jnp.uint32).reshape(1), B_pad,
        tb=tb, tn=tn, interpret=interpret)
    return M[:5, :B].T


@functools.partial(jax.jit, static_argnames=("B", "tb", "tn", "interpret"))
def bootstrap_moments_masked(
    x: jax.Array,          # (..., n) sample values, any leading dims
    mask: jax.Array,       # (..., n) validity
    seeds: jax.Array,      # (...,) uint32 counter seeds, one per group
    B: int = 500,
    *,
    lane_active: jax.Array | None = None,  # (...,) gate flags, None = all on
    tb: int = 256,
    tn: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """(..., B, 5) replicate moment sums for a batch of masked groups.

    The fused-loop entry point: the caller (core/fused.py) slices its carried
    sample buffer to the active width bucket and hands the slice here with
    the per-(lane, group) counter seeds.  Weight entry (j, b) is
    ``poisson1(hash3(seed, j, b))`` with j the ABSOLUTE slot index, so the
    replicate sums do not depend on the bucket width -- only masked rows
    contribute, and their draws are width-invariant.  ``ref.py``'s
    :func:`~..ref.bootstrap_moments_masked_ref` materializes the same weight
    matrix in jnp; interpret-mode parity is bit-comparable up to f32
    accumulation order.

    ``lane_active`` gates whole groups at grid level (``pl.when`` inside the
    kernel): an inactive group's tiles neither generate weights nor touch
    the MXU, and its replicate sums come back as zeros.  Callers may only
    pass it when they discard inactive groups' outputs -- the fused loop's
    frozen-lane predication -- because zeros are NOT the ungated result for
    those groups.  Active groups are bit-equal with any flag pattern.
    """
    if interpret is None:
        interpret = interpret_default()
    lead = x.shape[:-1]
    n = x.shape[-1]
    n_pad = _round_up(n, tn)
    B_pad = _round_up(B, tb)
    xf = x.reshape((-1, n))
    mf = mask.reshape((-1, n))
    sf = seeds.reshape((-1,)).astype(jnp.uint32)
    if lane_active is None:
        act = jnp.ones((xf.shape[0],), jnp.int32)
    else:
        act = lane_active.reshape((-1,)).astype(jnp.int32)
    feats = jax.vmap(lambda xg, mg: build_feats(xg, mg, n_pad))(xf, mf)
    M = K.poisson_bootstrap_moments_lanes(
        feats, sf, act, B_pad, tb=tb, tn=tn, interpret=interpret)
    return M[:, :5, :B].transpose(0, 2, 1).reshape(lead + (B, 5))


@functools.partial(
    jax.jit,
    static_argnames=("est_name", "B", "metric", "tb", "tn", "interpret"))
def estimate_error_moments(
    est_name: str,
    sample: jax.Array,     # (m, n_cap, c)
    mask: jax.Array,       # (m, n_cap)
    scale: jax.Array,      # (m,)
    key: jax.Array,
    delta,
    B: int = 500,
    metric: str = "l2",
    active: jax.Array | None = None,   # (m,) group gate flags, None = all on
    tb: int = 256,
    tn: int = 512,
    interpret: bool | None = None,
):
    """Kernel-backed ESTIMATE: mirrors core.bootstrap.estimate_error.

    ``active`` forwards to the kernel's grid-level gating: inactive groups
    skip their bootstrap tiles and contribute ZERO per-group error to the
    joint metric (their theta falls back to the plain-sample estimate via
    the dead-replicate guard).  Only pass it when the caller discards or
    re-derives those groups' contributions.
    """
    est = get_estimator(est_name)
    if est.moments_finish is None:
        raise ValueError(f"{est_name} is not a moment estimator")
    m = sample.shape[0]
    seeds = jax.random.randint(key, (m,), 0, jnp.iinfo(jnp.int32).max)
    v = sample[..., 0]
    M = bootstrap_moments_masked(
        v, mask, seeds.astype(jnp.uint32), B, lane_active=active,
        tb=tb, tn=tn, interpret=interpret)                     # (m, B, 5)
    # Guard dead replicates (sum w == 0): substitute the plain sample.
    mf = mask.astype(jnp.float32)
    feats = jnp.stack([mf, mf * v, mf * v * v], axis=-1)       # (m, n, 3)
    M_plain = jnp.einsum("mn,mnp->mp", mf, feats,
                         precision="highest")                  # (m, 3)
    dead = M[:, :, 0:1] <= 0
    M3 = jnp.where(dead, M_plain[:, None, :], M[:, :, :3])
    reps = est.moments_finish(M3)                              # (m, B, 1)
    theta_hat = est.moments_finish(M_plain[:, None, :])[:, 0, :]  # (m, 1)
    errs = jnp.sqrt(jnp.sum((reps - theta_hat[:, None, :]) ** 2, axis=-1))
    errs = errs * scale[:, None]
    joint = _joint_metric(errs, metric, axis=0)
    e = jnp.quantile(joint, 1.0 - delta)
    return e, theta_hat * scale[:, None]
