"""Counter-based in-kernel PRNG shared by Pallas kernels and their oracles.

A murmur3-finalizer hash of (seed, row, col) gives stateless, order-
independent uniforms: the kernel generates the (row, col) entry of the
bootstrap weight matrix on the fly in VMEM, and ref.py materializes the very
same matrix in pure jnp -- so kernel tests can compare against the oracle
with tight tolerances instead of only statistically.

Why not ``pltpu.prng_random_bits``: the hardware PRNG is stateful (seeded per
core), which couples the random stream to the grid schedule; the cost of the
counter hash (6 int ops / draw) is negligible next to the streamed matmul,
and it keeps interpret-mode CPU validation bit-identical to the TPU target.

All arithmetic is uint32 with wrapping semantics (defined in jnp and Mosaic).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# NOTE: all multiplier constants are inline np.uint32 scalars (strong-typed
# literals) -- module-level jnp scalars would be captured as external consts
# by the Pallas kernel tracer, and bare Python ints > int32 max overflow the
# weak-type parser.


def mix32(h):
    """murmur3 finalizer: full avalanche on 32 bits."""
    h = h.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * np.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def hash3(seed, row, col):
    """Stateless uniform bits for matrix entry (row, col) under ``seed``."""
    seed = seed.astype(jnp.uint32) if hasattr(seed, "astype") else jnp.uint32(seed)
    row = row.astype(jnp.uint32)
    col = col.astype(jnp.uint32)
    return mix32(row * np.uint32(0x9E3779B1) ^ col * np.uint32(0x85EBCA77) ^ seed * np.uint32(0xC2B2AE3D))


def uniform01(bits):
    """uint32 bits -> f32 uniform in [0, 1) using the top 24 bits.

    The cast goes through int32 because Mosaic has no uint32 -> f32 cast;
    ``bits >> 8`` is below 2**24, so both casts are exact and the result is
    bit-identical to a direct conversion.
    """
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (2.0**-24)


# Poisson(1) CDF ladder -- MUST stay identical to
# repro.core.bootstrap._POISSON1_CDF so the jnp path, the kernel and the
# oracle all sample the same distribution.
POISSON1_CDF = (
    0.36787944117144233, 0.7357588823428847, 0.9196986029286058,
    0.9810118431238462, 0.9963401531726563, 0.9994058151824183,
    0.9999167588507119, 0.9999897508033253, 0.9999988747974149,
    0.9999998885745217,
)


def poisson1_from_uniform(u):
    """Inverse-CDF Poisson(1) counts from uniforms (truncated at 10)."""
    w = jnp.zeros(u.shape, jnp.float32)
    for c in POISSON1_CDF:
        w = w + (u >= jnp.float32(c)).astype(jnp.float32)
    return w


def poisson1_weights_at(seed, row, col):
    """Fused: weight matrix entry (row, col) = Poisson(1) draw."""
    return poisson1_from_uniform(uniform01(hash3(seed, row, col)))
