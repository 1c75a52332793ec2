"""Distributed AQP over a mesh-sharded dataset (shard_map + psum).

The Poisson bootstrap COMPOSES over shards: replicate b's moment sums
M_b = sum_j w_bj * feats_j split over row shards as M_b = sum_shards M_b^s
with independent Poisson weights per shard.  So the whole distributed
ESTIMATE is: shard-local (sample -> weight -> moment-matmul), one psum of
a (m, B, 3) tensor, finishers on the (tiny) reduced result.  Only
m * B * 3 floats cross the interconnect regardless of data size -- the
TPU-native replacement for the paper's "avoid full scans via gap sampling
+ inverted index" (DESIGN.md SS3).

Also provides the exact distributed GROUP BY (segment_agg partials + psum).
"""
from __future__ import annotations

from functools import lru_cache
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import estimators
# Mesh construction and row-sharding live in core/mesh.py (shared with the
# sharded lane pool); re-exported here for compatibility.
from ..core.mesh import make_data_mesh, shard_dataset  # noqa: F401
from ..kernels import prng

Array = jax.Array


@lru_cache(maxsize=16)
def _group_stats_fn(mesh, m: int):
    """Jit-compiled exact GROUP BY for one (mesh, m) -- memoized so repeat
    calls reuse the compiled program instead of re-wrapping per call
    (misslint ML302)."""

    def local(gid_l, x_l):
        valid = (gid_l >= 0).astype(jnp.float32)
        g = jnp.maximum(gid_l, 0)
        onehot = jax.nn.one_hot(g, m, dtype=jnp.float32) * valid[:, None]
        cnt = jnp.sum(onehot, axis=0)
        s1 = jnp.matmul(onehot.T, x_l, precision="highest")
        s2 = jnp.matmul(onehot.T, x_l * x_l, precision="highest")
        big = jnp.float32(3e38)
        mn = jnp.min(jnp.where(onehot.T > 0, x_l[None, :], big), axis=1)
        mx = jnp.max(jnp.where(onehot.T > 0, x_l[None, :], -big), axis=1)
        cnt = jax.lax.psum(cnt, "data")
        s1 = jax.lax.psum(s1, "data")
        s2 = jax.lax.psum(s2, "data")
        mn = jax.lax.pmin(mn, "data")
        mx = jax.lax.pmax(mx, "data")
        return cnt, s1, s2, mn, mx

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P(), P(), P(), P())))


def sharded_group_stats(mesh, gid: Array, x: Array, m: int):
    """Exact distributed GROUP BY count/sum/sumsq/min/max via psum."""
    cnt, s1, s2, mn, mx = _group_stats_fn(mesh, m)(gid, x)
    return {"count": cnt, "sum": s1, "sumsq": s2, "min": mn, "max": mx}


@lru_cache(maxsize=16)
def _bootstrap_fn(mesh, m: int, B: int):
    """Jit-compiled sharded sample+bootstrap body for one (mesh, m, B).

    ``rate`` and the two seeds are TRACED (replicated) operands rather than
    closure captures: baking them in as constants would both defeat this
    memo (a new program per MISS iteration's rate) and silently pin stale
    values (misslint ML302's failure mode)."""

    def local(gid_l, x_l, rate_r, boot_seed, samp_seed):
        n_l = gid_l.shape[0]
        shard = jax.lax.axis_index("data")
        valid = gid_l >= 0
        g = jnp.maximum(gid_l, 0)
        # --- shard-local Bernoulli(rate_g) sampling via counter PRNG ---
        rows = jnp.arange(n_l, dtype=jnp.uint32)
        u = prng.uniform01(prng.hash3(
            samp_seed, rows, jnp.full_like(rows, shard)))
        sampled = valid & (u < rate_r[g])
        w_mask = sampled.astype(jnp.float32)
        feats = jnp.stack([w_mask, w_mask * x_l, w_mask * x_l * x_l], axis=1)
        onehot = jax.nn.one_hot(g, m, dtype=jnp.float32) * w_mask[:, None]
        # --- replicate weights: Poisson(1) per (row, replicate) ---
        cols = jnp.arange(1, B + 1, dtype=jnp.uint32)
        w = prng.poisson1_weights_at(
            boot_seed,
            rows[:, None] + shard * jnp.uint32(n_l), cols[None, :])  # (n,B)
        # replicate 0 = the plain sample (weights all 1).
        w_all = jnp.concatenate([jnp.ones((n_l, 1), jnp.float32), w], axis=1)
        # M[g, b, p] = sum_rows onehot[row,g] * w_all[row,b] * feats[row,p]
        M = jnp.einsum("ng,nb,np->gbp", onehot, w_all, feats,
                       precision="highest")
        return jax.lax.psum(M, "data")

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P(), P(), P()),
        out_specs=P()))


def sharded_bootstrap_estimate(
    mesh, gid: Array, x: Array, m: int, rate: Array, seed: int,
    *, B: int = 200, delta: float = 0.05, est_name: str = "avg",
    sample_seed: "int | None" = None,
) -> Tuple[Array, Array]:
    """Distributed (sample -> Poisson bootstrap -> L2 error, theta-hat).

    ``rate (m,)``: per-group Bernoulli sampling rate (n_g / |D|_g). Rows are
    sampled shard-locally; every replicate's moments are shard-local
    matmuls; one psum of (m, B+1, 3) crosses the network.

    ``sample_seed`` is the distributed analogue of the SampleStore's permuted
    prefix (DESIGN.md SS3.2): each row's keep-threshold u is a pure function
    of (sample_seed, row, shard), i.e. a shard-local permutation of the rows
    ordered by u, and Bernoulli(rate) keeps exactly the u < rate prefix of
    it.  Calling again with a larger ``rate`` and the SAME ``sample_seed``
    therefore yields a strict superset of rows -- MISS iterations refine,
    not replace, the sample, and the psum contract ((m, B+1, 3) partials)
    is unchanged.  Defaults to ``seed`` (bootstrap weights use a distinct
    derived stream either way); pass a fixed value across iterations to get
    nested samples while re-randomizing the bootstrap via ``seed``.
    """
    est = estimators.get(est_name)
    if est.moments_finish is None:
        raise ValueError(f"{est_name} is not a moment estimator")
    if sample_seed is None:
        sample_seed = seed
    boot_seed = (seed ^ 0x5BD1E995) & 0xFFFFFFFF
    M = _bootstrap_fn(mesh, m, B)(
        gid, x, rate,
        jnp.uint32(boot_seed), jnp.uint32(sample_seed))  # (m, B+1, 3)
    theta = est.moments_finish(M[:, 0])        # (m, 1)
    reps = est.moments_finish(M[:, 1:])        # (m, B, 1)
    err = jnp.sqrt(jnp.sum((reps - theta[:, None]) ** 2, axis=-1))  # (m, B)
    joint = jnp.sqrt(jnp.sum(err**2, axis=0))
    e = jnp.quantile(joint, 1.0 - delta)
    return e, theta[:, 0]
