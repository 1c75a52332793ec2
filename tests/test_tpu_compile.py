"""Ahead-of-time compiles of the ESTIMATE kernels for a TPU v5e chip.

Interpret-mode parity (test_kernels.py) cannot see what Mosaic refuses:
unsupported casts, unaligned tiles, more VMEM than a kernel may use.  These
tests compile each kernel of the serving path for a described ``v5e:2x2``
topology -- no chip attached -- at the widths the lane pool runs (2^16
slots, B=300), and check that the program holds the Pallas kernel
(``tpu_custom_call``).  The topology is described inside a fixture, so
only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.poisson_bootstrap import kernel as pb_kernel
from repro.kernels.poisson_bootstrap import ops as pb_ops
from repro.kernels.segment_agg import kernel as seg_kernel

WIDTH = 1 << 16     # the pool's n_cap: slots per (lane, group)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a program compiled for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _program(name, sharding):
    """``(fn, argument shapes)`` of one kernel at pool widths."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32
    if name == "poisson_bootstrap_moments_lanes":
        return (lambda f, s, a: pb_kernel.poisson_bootstrap_moments_lanes(
                    f, s, a, 512),
                [arg((8, pb_kernel.P, WIDTH), f32), arg((8,), u32),
                 arg((8,), i32)])
    if name == "bootstrap_moments_masked":
        # q=8 lanes x m=3 groups, the tick's ESTIMATE at its widest bucket.
        return (lambda x, m, s: pb_ops.bootstrap_moments_masked(
                    x, m, s, 300, interpret=False),
                [arg((8, 3, WIDTH), f32), arg((8, 3, WIDTH), f32),
                 arg((8, 3), u32)])
    if name == "segment_boot_call":
        return (lambda f, g, sl, sd: seg_kernel.segment_boot_call(
                    f, g, sl, sd, m_pad=256, B_pad=512),
                [arg((seg_kernel.P, WIDTH), f32), arg((1, WIDTH), i32),
                 arg((1, WIDTH), i32), arg((1, WIDTH), u32)])
    assert name == "segment_agg_call"
    return (lambda f, g, x, m: seg_kernel.segment_agg_call(
                f, g, x, m, m_pad=128),
            [arg((seg_kernel.P, WIDTH), f32), arg((1, WIDTH), i32),
             arg((1, WIDTH), f32), arg((1, WIDTH), f32)])


@pytest.mark.parametrize("name", [
    "poisson_bootstrap_moments_lanes", "bootstrap_moments_masked",
    "segment_boot_call", "segment_agg_call"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _program(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
