"""Pallas TPU kernel: fused Poisson-bootstrap moment accumulation.

Computes, for B bootstrap replicates over an n-row sample,

    M[p, b] = sum_j feats[p, j] * W[j, b],     W[j, b] ~ Poisson(1) iid

where feats rows are the masked moment features [m, m*x, m*x^2, m*x^3,
m*x^4, 0, 0, 0].  The weight matrix W (n x B -- up to 500x the sample size)
is NEVER materialized in HBM: each (tn x tb) tile is generated inside the
kernel from the counter-based PRNG (kernels/prng.py) and immediately
contracted against the resident feats tile on the MXU.

TPU adaptation story (DESIGN.md SS3): the paper's bootstrap is a gather-heavy
CPU loop (B resamples x n index lookups).  Gathers bypass the MXU and thrash
HBM on TPU; this kernel converts the resampling into a streaming matmul with
O(B) FLOPs per byte of sample data -- compute-bound instead of gather-bound.

Grid-level predication (DESIGN.md SS7 phase E): the lane-batched entry
carries a per-group ``active`` vector as a scalar-prefetch operand, and
every grid tile of an inactive group early-exits under ``pl.when`` -- the
weight generation and the MXU contraction are SKIPPED, not masked, so a
lane pool's frozen/parked lanes cost zero kernel tiles instead of full
tiles of discarded work.  Inactive groups report zero sums (their output
block is only ever touched by the init write).  Active groups execute the
identical tile sequence whatever their neighbors' flags are, so gated and
ungated results are bit-equal on active groups.

Memory plan per grid step (defaults tb=256, tn=512):
    feats tile  (8, tn)   VMEM   16 KiB
    W tile      (tn, tb)  VMEM  512 KiB (generated in-register, never in HBM)
    acc tile    (8, tb)   VMEM    8 KiB (revisited across the n-grid axis)
Grid = (G, B/tb, n/tn); the n axis is innermost so the accumulator tile
stays resident while the kernel streams one group's sample exactly once per
B-tile, and the group axis is outermost so predication skips whole
per-group tile rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import prng

P = 8  # feature rows (moments 0..4 + padding to the f32 sublane tile)


def _kernel(seed_ref, active_ref, feats_ref, out_ref, *, tb: int, tn: int):
    g = pl.program_id(0)
    b_idx = pl.program_id(1)
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active_ref[g] != 0)
    def _accumulate():
        # Generate the (tn x tb) Poisson(1) weight tile from the counter
        # PRNG.  Row/col offsets are ABSOLUTE, so the draws are a pure
        # function of (seed, slot, replicate) -- width- and tile-invariant.
        rows = n_idx * tn + jax.lax.broadcasted_iota(jnp.uint32, (tn, tb), 0)
        cols = b_idx * tb + jax.lax.broadcasted_iota(jnp.uint32, (tn, tb), 1)
        w = prng.poisson1_weights_at(seed_ref[g], rows, cols)
        # (P, tn) @ (tn, tb) -> (P, tb) on the MXU; accumulate in f32.  At
        # the default precision the MXU rounds the f32 features to bf16,
        # ~1e-4 relative error in the moment sums on a v5e.
        out_ref[0] += jnp.dot(
            feats_ref[0], w, precision="highest",
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("B_pad", "tb", "tn", "interpret"))
def poisson_bootstrap_moments_lanes(
    feats: jax.Array,     # (G, P, n_pad) masked moment features, f32
    seeds: jax.Array,     # (G,) uint32 counter seeds, one per group
    active: jax.Array,    # (G,) int32 gating flags (0 -> skip, output zeros)
    B_pad: int | None = None,
    *,
    tb: int = 256,
    tn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns (G, P, B_pad): M[g, p, b] = sum_j feats[g, p, j] * W_g[j, b].

    Groups with ``active[g] == 0`` skip weight generation and the MXU
    contraction at grid level (``pl.when``) and return zeros; active groups
    are bit-equal to an all-active call.  ``active`` is a traced operand
    (scalar prefetch), so flipping flags between calls never recompiles.
    """
    if B_pad is None:
        B_pad = tb
    G, p_dim, n_pad = feats.shape
    if p_dim != P:
        raise ValueError(f"feats must have {P} rows, got {feats.shape}")
    if n_pad % tn or B_pad % tb:
        raise ValueError(f"n_pad {n_pad} % tn {tn} or B_pad {B_pad} % tb {tb}")
    grid = (G, B_pad // tb, n_pad // tn)
    return pl.pallas_call(
        functools.partial(_kernel, tb=tb, tn=tn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, P, tn), lambda g, b, n, seeds, act: (g, 0, n)),
            ],
            out_specs=pl.BlockSpec(
                (1, P, tb), lambda g, b, n, seeds, act: (g, 0, b)),
        ),
        out_shape=jax.ShapeDtypeStruct((G, P, B_pad), jnp.float32),
        interpret=interpret,
    )(seeds.astype(jnp.uint32), active.astype(jnp.int32), feats)


@functools.partial(jax.jit, static_argnames=("B_pad", "tb", "tn", "interpret"))
def poisson_bootstrap_moments(
    feats: jax.Array,     # (P, n_pad) masked moment features, f32
    seed: jax.Array,      # (1,) uint32 counter seed
    B_pad: int | None = None,
    *,
    tb: int = 256,
    tn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Single-group entry: (P, B_pad) = feats @ W.  The G=1 configuration of
    :func:`poisson_bootstrap_moments_lanes` (always active), kept for the
    per-group callers and the kernel-vs-oracle tests."""
    return poisson_bootstrap_moments_lanes(
        feats[None], seed.reshape(1), jnp.ones((1,), jnp.int32), B_pad,
        tb=tb, tn=tn, interpret=interpret)[0]
