"""Pallas TPU kernel: fused one-pass GROUP BY aggregation.

For each group g <= m and a stream of (group_id, value) rows, computes
    moments[p, g] = sum_{j : gid_j = g} x_j^p        (p = 0..4, masked)
    mn[g]        = min_{j : gid_j = g} x_j
    mx[g]        = max_{j : gid_j = g} x_j

TPU adaptation (DESIGN.md SS3): scatter-adds (segment_sum) are serialized on
TPU; instead each tile contracts moment features against an on-the-fly
one-hot group matrix on the MXU:

    moments_tile = feats (P, tn) . onehot^T (tn, m)   [dot_general]

and min/max are masked VPU reductions over the same one-hot.  One streaming
pass over the data, group table resident in VMEM.  This kernel powers the
AQP engine's exact GROUP BY answers and the per-shard partial aggregation
whose (m x P) partials are psum'd across the data mesh axis.

Blocks: feats (P, tn), gid (1, tn) int32, x (1, tn); outputs
moments (P, m_pad), mn/mx (8, m_pad) (row-replicated).  Grid = (n/tn,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import prng

P = 8
NEG_INF = -3.0e38
POS_INF = 3.0e38


def _kernel(feats_ref, gid_ref, x_ref, mask_ref,
            mom_ref, mn_ref, mx_ref, *, tn: int, m_pad: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        mom_ref[...] = jnp.zeros_like(mom_ref)
        mn_ref[...] = jnp.full_like(mn_ref, POS_INF)
        mx_ref[...] = jnp.full_like(mx_ref, NEG_INF)

    gid = gid_ref[...]                      # (1, tn) int32
    x = x_ref[...]                          # (1, tn) f32
    valid = mask_ref[...] > 0               # (1, tn)
    groups = jax.lax.broadcasted_iota(jnp.int32, (m_pad, tn), 0)
    onehot = (jnp.broadcast_to(gid, (m_pad, tn)) == groups) & jnp.broadcast_to(
        valid, (m_pad, tn))                 # (m_pad, tn) bool
    # MXU: (P, tn) x (m_pad, tn) contracting tn -> (P, m_pad).
    mom_ref[...] += jax.lax.dot_general(
        feats_ref[...], onehot.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())), precision="highest",
        preferred_element_type=jnp.float32)
    # VPU: masked min/max per group, broadcast across the 8 sublane rows.
    xb = jnp.broadcast_to(x, (m_pad, tn))
    tile_mn = jnp.min(jnp.where(onehot, xb, POS_INF), axis=1)   # (m_pad,)
    tile_mx = jnp.max(jnp.where(onehot, xb, NEG_INF), axis=1)
    mn_ref[...] = jnp.minimum(mn_ref[...], jnp.broadcast_to(tile_mn, (P, m_pad)))
    mx_ref[...] = jnp.maximum(mx_ref[...], jnp.broadcast_to(tile_mx, (P, m_pad)))


def _boot_kernel(feats_ref, gid_ref, slot_ref, seed_ref, out_ref,
                 *, tb: int, tn: int, m_pad: int):
    """Segment-aggregated Poisson-bootstrap replicate moments.

    Tile (b_i, n_i): contracts the masked moment features of ``tn`` packed
    stream elements against an on-the-fly one-hot lane matrix, weighted by
    ``tb`` counter-PRNG Poisson(1) replicate columns generated in VMEM --
    the grouped-block analogue of ``poisson_bootstrap``: one pass over the
    SHARED gathered rows yields count/sum/sumsq replicate sums for every
    lane.  Weight (j, b) hashes the element's own (seed, absolute slot)
    pair, so a lane's replicate stream is identical to the per-lane path's
    regardless of where its window lands in the packed stream.
    """
    b_i = pl.program_id(0)
    n_i = pl.program_id(1)

    @pl.when(n_i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gid = gid_ref[...]                      # (1, tn) int32 lane ids
    groups = jax.lax.broadcasted_iota(jnp.int32, (m_pad, tn), 0)
    valid = feats_ref[0:1, :] > 0           # count-feature row encodes mask
    onehot = ((jnp.broadcast_to(gid, (m_pad, tn)) == groups)
              & jnp.broadcast_to(valid, (m_pad, tn))).astype(jnp.float32)
    # Replicate weights (tb, tn): row b, element j -> poisson1(hash3(seed_j,
    # slot_j, b)).  seed/slot broadcast along the replicate axis (no
    # transposes), the absolute replicate index comes from the grid.
    slot = jnp.broadcast_to(slot_ref[...], (tb, tn)).astype(jnp.uint32)
    seed = jnp.broadcast_to(seed_ref[...], (tb, tn)).astype(jnp.uint32)
    rep = (jax.lax.broadcasted_iota(jnp.uint32, (tb, tn), 0)
           + (b_i * tb).astype(jnp.uint32))
    w = prng.poisson1_from_uniform(prng.uniform01(prng.hash3(seed, slot, rep)))
    # MXU: (m_pad, tn) x (tb, tn) contracting tn -> (m_pad, tb), one per
    # moment power.
    mom = [
        jax.lax.dot_general(
            onehot, w * feats_ref[p:p + 1, :],
            dimension_numbers=(((1,), (1,)), ((), ())), precision="highest",
            preferred_element_type=jnp.float32)
        for p in range(3)
    ]
    out_ref[...] += jnp.stack(mom)


@functools.partial(
    jax.jit, static_argnames=("m_pad", "B_pad", "tb", "tn", "interpret"))
def segment_boot_call(
    feats: jax.Array,   # (P, n_pad) masked moment features [m, mx, mx^2, 0..]
    gid: jax.Array,     # (1, n_pad) int32 lane ids (padding: any id, mask 0)
    slot: jax.Array,    # (1, n_pad) int32 ABSOLUTE buffer slot per element
    seed: jax.Array,    # (1, n_pad) uint32 per-element lane bootstrap seed
    *,
    m_pad: int,
    B_pad: int,
    tb: int = 256,
    tn: int = 512,
    interpret: bool = False,
):
    n_pad = feats.shape[1]
    assert n_pad % tn == 0 and m_pad % 128 == 0 and B_pad % tb == 0
    grid = (B_pad // tb, n_pad // tn)
    return pl.pallas_call(
        functools.partial(_boot_kernel, tb=tb, tn=tn, m_pad=m_pad),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((P, tn), lambda b, i: (0, i)),
                pl.BlockSpec((1, tn), lambda b, i: (0, i)),
                pl.BlockSpec((1, tn), lambda b, i: (0, i)),
                pl.BlockSpec((1, tn), lambda b, i: (0, i)),
            ],
            out_specs=pl.BlockSpec((3, m_pad, tb), lambda b, i: (0, 0, b)),
        ),
        out_shape=jax.ShapeDtypeStruct((3, m_pad, B_pad), jnp.float32),
        interpret=interpret,
    )(feats, gid, slot, seed)


@functools.partial(
    jax.jit, static_argnames=("m_pad", "tn", "interpret"))
def segment_agg_call(
    feats: jax.Array,   # (P, n_pad) masked moment features
    gid: jax.Array,     # (1, n_pad) int32 group ids (padding rows: any id)
    x: jax.Array,       # (1, n_pad) f32 values
    mask: jax.Array,    # (1, n_pad) f32 validity
    *,
    m_pad: int,
    tn: int = 1024,
    interpret: bool = False,
):
    n_pad = feats.shape[1]
    assert n_pad % tn == 0 and m_pad % 128 == 0
    grid = (n_pad // tn,)
    return pl.pallas_call(
        functools.partial(_kernel, tn=tn, m_pad=m_pad),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((P, tn), lambda i: (0, i)),
                pl.BlockSpec((1, tn), lambda i: (0, i)),
                pl.BlockSpec((1, tn), lambda i: (0, i)),
                pl.BlockSpec((1, tn), lambda i: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((P, m_pad), lambda i: (0, 0)),
                pl.BlockSpec((P, m_pad), lambda i: (0, 0)),
                pl.BlockSpec((P, m_pad), lambda i: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((P, m_pad), jnp.float32),
            jax.ShapeDtypeStruct((P, m_pad), jnp.float32),
            jax.ShapeDtypeStruct((P, m_pad), jnp.float32),
        ],
        interpret=interpret,
    )(feats, gid, x, mask)
