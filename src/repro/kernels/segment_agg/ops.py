"""jit'd wrapper for the segment_agg kernel: GROUP BY <g> AGG(x) in one pass."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_default
from . import kernel as K


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


@functools.partial(jax.jit, static_argnames=("m", "tn", "interpret"))
def segment_aggregate(
    gid: jax.Array,    # (n,) int32 group ids in [0, m)
    x: jax.Array,      # (n,) f32 values
    mask: jax.Array,   # (n,) validity
    m: int,
    *,
    tn: int = 1024,
    interpret: bool | None = None,
):
    """Per-group aggregates dict: count/sum/sumsq/sum3/sum4/min/max (m,).

    One kernel pass covers m <= m_pad = 128 groups; larger group counts are
    tiled across ceil(m / 128) passes over the same stream -- pass p masks
    the stream down to groups [128p, 128(p+1)) and shifts their ids into
    the pass-local range, so every pass runs the identical 128-wide kernel.
    """
    if interpret is None:
        interpret = interpret_default()
    if m > 128:
        gid = gid.astype(jnp.int32)
        mf = mask.astype(jnp.float32)
        parts = []
        for g0 in range(0, m, 128):
            sub = min(128, m - g0)
            in_pass = ((gid >= g0) & (gid < g0 + sub)).astype(jnp.float32)
            parts.append(segment_aggregate(
                jnp.clip(gid - g0, 0, sub - 1), x, mf * in_pass, sub,
                tn=tn, interpret=interpret))
        return {k: jnp.concatenate([p[k] for p in parts]) for k in parts[0]}
    n = gid.shape[0]
    n_pad = _round_up(max(n, tn), tn)
    pad = n_pad - n
    xf = jnp.pad(x.astype(jnp.float32), (0, pad))
    mf = jnp.pad(mask.astype(jnp.float32), (0, pad))
    gf = jnp.pad(gid.astype(jnp.int32), (0, pad))
    x2 = xf * xf
    feats = jnp.stack(
        [mf, mf * xf, mf * x2, mf * x2 * xf, mf * x2 * x2,
         jnp.zeros_like(xf), jnp.zeros_like(xf), jnp.zeros_like(xf)], axis=0)
    mom, mn, mx = K.segment_agg_call(
        feats, gf[None, :], xf[None, :], mf[None, :],
        m_pad=128, tn=tn, interpret=interpret)
    return {
        "count": mom[0, :m], "sum": mom[1, :m], "sumsq": mom[2, :m],
        "sum3": mom[3, :m], "sum4": mom[4, :m],
        "min": mn[0, :m], "max": mx[0, :m],
    }


@functools.partial(
    jax.jit, static_argnames=("m", "B", "tb", "tn", "interpret"))
def segment_bootstrap_moments(
    gid: jax.Array,    # (n,) int32 lane ids in [0, m)
    slot: jax.Array,   # (n,) int32 ABSOLUTE buffer slot of each element
    x: jax.Array,      # (n,) f32 values
    mask: jax.Array,   # (n,) validity
    seed: jax.Array,   # (n,) uint32 per-element lane bootstrap seed
    m: int,
    B: int,
    *,
    tb: int = 256,
    tn: int = 512,
    interpret: bool | None = None,
):
    """(m, B, 3) per-lane Poisson-bootstrap replicate moment sums.

    Row b of lane g is ``[sum w, sum w x, sum w x^2]`` over the lane's
    packed stream elements, with weight (j, b) = ``poisson1(hash3(seed_j,
    slot_j, b))`` -- the identical draw the per-lane bootstrap paths make
    for (lane, absolute slot, replicate), so a lane's sums here match its
    solo run's up to f32 summation order.  One pass over the SHARED packed
    stream serves every lane: cost tracks the stream length (the union
    watermark of the block), not ``m x n_cap``.
    """
    if interpret is None:
        interpret = interpret_default()
    n = gid.shape[0]
    n_pad = _round_up(max(n, tn), tn)
    pad = n_pad - n
    xf = jnp.pad(x.astype(jnp.float32), (0, pad))
    mf = jnp.pad(mask.astype(jnp.float32), (0, pad))
    gf = jnp.pad(gid.astype(jnp.int32), (0, pad))
    sf = jnp.pad(slot.astype(jnp.int32), (0, pad))
    sd = jnp.pad(seed.astype(jnp.uint32), (0, pad))
    feats = jnp.stack(
        [mf, mf * xf, mf * xf * xf] + [jnp.zeros_like(xf)] * 5, axis=0)
    m_pad = _round_up(max(m, 1), 128)
    B_pad = _round_up(B, tb)
    out = K.segment_boot_call(
        feats, gf[None, :], sf[None, :], sd[None, :],
        m_pad=m_pad, B_pad=B_pad, tb=tb, tn=tn, interpret=interpret)
    return jnp.moveaxis(out, 0, -1)[:m, :B, :]
