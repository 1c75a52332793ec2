"""Ahead-of-time compiles of the ESTIMATE kernels for a TPU v5e chip.

Interpret-mode parity (test_kernels.py) cannot see what Mosaic refuses:
unsupported casts, unaligned tiles, more VMEM than a kernel may use.  These
tests compile each kernel of the serving path for a described ``v5e:2x2``
topology -- no chip attached -- at the widths the lane pool runs (2^16
slots, B=300), and check that the program holds the Pallas kernel
(``tpu_custom_call``).  The topology is described inside a fixture, so
only the worker that runs this file loads the TPU compiler.

The lane pool's tier and grouped block steps are compiled the same way at
the row count of TPC-H lineitem SF 10, and its row-sharded tier step for
the four chips of the described ``v5e:2x2`` at SF 300's (one 449,997,273-row
block of each column a chip), to check that the table reaches the row
gathers as it is passed in, with no whole-table relayout in the tick.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.aqp.query import Query
from repro.core import mesh as core_mesh
from repro.core.sampling import GroupedData
from repro.kernels.poisson_bootstrap import kernel as pb_kernel
from repro.kernels.poisson_bootstrap import ops as pb_ops
from repro.kernels.segment_agg import kernel as seg_kernel
from repro.kernels.segment_agg import ops as seg_ops
from repro.serve.lane_pool import LanePool

WIDTH = 1 << 16     # the pool's n_cap: slots per (lane, group)
SF10_ROWS = 59_986_052
# L_RETURNFLAG's groups A, N, R at SF 10 (24.7%, 50.6%, 24.7%).
SF10_OFFSETS = [0, 14_816_555, 45_169_497, SF10_ROWS]
# Lineitem SF 300 by L_RETURNFLAG (1,799,989,091 rows), padded to four
# row shards of the same size.
SF300_OFFSETS = [0, 444_191_988, 1_355_798_201, 1_799_989_091]
SF300_SHARDS = 4
SF300_SHARD_ROWS = 449_997_273


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: a
    program compiled for a described chip is written to the cache but
    cannot be read back without one."""
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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# Opcodes that may carry a whole-table shape without moving the table:
# the parameters, tuples and control flow that pass it through to the
# gathers, and the gathers themselves.
_PASS_THROUGH = {"parameter", "tuple", "get-tuple-element", "while",
                 "conditional", "call", "gather"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (.+?) ([a-z][\w\-]*)\(")


def _whole_table_ops(hlo_text, rows):
    """``(opcode, line)`` of each instruction whose result has the table's
    row count ``rows`` and which is not a pass-through or a gather."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if (m and re.search(rf"\b{rows}\b", m.group(1))
                and m.group(2) not in _PASS_THROUGH):
            out.append((m.group(2), line.strip()[:160]))
    return out


def _sharded_tier_program(topo, c):
    """The row-sharded pool's tier step for the four described chips at SF
    300, with its arguments as shapes placed as the mesh pool places them.

    The pool is built on one device (``mesh=False``, the same layout and
    step parameters) around a stand-in table of SF 300's groups, which no
    step reads; then given the described chips' mesh."""
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    data = GroupedData(jnp.zeros((8, c), jnp.float32), SF300_OFFSETS)
    with pytest.MonkeyPatch.context() as mp:
        # Keep the stand-in small: no padding to SF 300's rows.
        mp.setattr(core_mesh, "pad_rows", lambda v, rows: np.asarray(v))
        pool = LanePool(data, B=300, use_kernel=True,
                        data_shards=SF300_SHARDS, mesh=False)
    pool._mesh = mesh
    step, (cols, state, params, sspec), kw = pool._tier_program(
        pool._tiers[0])
    assert isinstance(cols, tuple) and len(cols) == c

    def shape(x, spec=P()):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    col = shape(jax.ShapeDtypeStruct((SF300_SHARDS * SF300_SHARD_ROWS,),
                                     jnp.float32), P("data"))
    state = jax.tree_util.tree_map(shape, state)._replace(
        buf=shape(state.buf, P(None, None, "data", None)))
    params = jax.tree_util.tree_map(shape, params)._replace(
        slot_idx=shape(params.slot_idx, P("data", None, None)))
    return step, ((col,) * c, state, params,
                  jax.tree_util.tree_map(shape, sspec)), kw


@pytest.mark.parametrize("c", [1, 2])
@pytest.mark.parametrize("kind", ["tier", "block", "sharded"])
def test_pool_step_has_no_whole_table_op(topo, one_chip, kind, c):
    """The pool's step at SF 10 (59,986,052 rows, abstract, no data) at the
    pool's default lanes, tiers and ``n_cap``, B=300, compiled for a v5e
    with the kernels in: only the row gathers touch the table.  ``sharded``:
    the row-sharded tier step over four chips at SF 300, where only the
    gathers touch a chip's 449,997,273-row block of a column."""
    with pytest.MonkeyPatch.context() as mp:
        for ops in (pb_ops, seg_ops):
            mp.setattr(ops, "interpret_default", lambda: False)
        if kind == "sharded":
            rows = SF300_SHARD_ROWS
            step, args, kw = _sharded_tier_program(topo, c)
            txt = step.lower(*args, **kw).compile().as_text()
        else:
            rows = SF10_ROWS
            # A stand-in table with SF 10's groups: the pool's shapes
            # follow the offsets, the step's table argument is replaced
            # below.
            data = GroupedData(jnp.zeros((8, c), jnp.float32), SF10_OFFSETS)
            pool = LanePool(data, B=300, use_kernel=True)
            if kind == "block":
                pool.submit_group(Query(func="avg", epsilon=0.05,
                                        group_by=True))
                step, args, kw = pool._block_program(*pool._block_shapes)
            else:
                step, args, kw = pool._tier_program(pool._tiers[0])
            assert isinstance(args[0], tuple) and len(args[0]) == c
            cols = tuple(jax.ShapeDtypeStruct((SF10_ROWS,), jnp.float32,
                                              sharding=one_chip)
                         for _ in range(c))
            rest = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                               sharding=one_chip), args[1:])
            txt = step.lower(cols, *rest, **kw).compile().as_text()
    assert "tpu_custom_call" in txt
    assert re.search(rf"\bgather\(", txt)
    assert _whole_table_ops(txt, rows) == []
