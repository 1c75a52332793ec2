"""Where a row-sharded lane pool puts its table (DESIGN.md phase G).

    JAX_PLATFORMS=cpu python -m pytest -q tests/test_sharded_placement.py

The multi-device checks run once, in a subprocess with four host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``; this file run as a
script), so that the test process keeps its one device:

* a table made row-sharded by ``aqpbench/tpch.py`` is taken as it is: no
  byte sent from the host, the same buffers on the same devices, also where
  its mesh orders the devices otherwise than ``make_data_mesh``;
* a host table, or one held otherwise on the devices, is placed so that
  each device receives its own block alone, and a mesh given in another
  device order gets each block moved to its place;
* from every placement, ``LanePool.values`` stays the 2-D row-sharded
  table, and the step's 1-D columns lie on the table's own mesh, each
  device holding its own block of each column, the same rows as its block
  of the table; ``table_bytes_by_device`` counts both;
* ``psum_bytes`` grows by the moment ``psum``'s payload, as the compiled
  step's ``all-reduce``s give it, at every tick;
* the mesh pool's answers are bit-equal to those of the ``mesh=False``
  pool of the same layout, from either placement.

In this process: the shard layout of TPC-H lineitem at SF 300 (1.8 billion
rows, group counts of a chip run) keeps its row indices inside int32.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
SPEC = dict(B=60, n_min=100, n_max=256, max_iters=8, n_cap=1 << 12)
ROWS = 40_001           # not a multiple of 4: the last shard is padded
SHARDS = 4


def _hlo_all_reduce_bytes(hlo: str) -> int:
    """Bytes of the results of the ``all-reduce``s in compiled HLO text."""
    size = {"f32": 4, "s32": 4, "u32": 4, "f64": 8, "bf16": 2}
    total = 0
    for line in hlo.splitlines():
        if not re.search(r"\ball-reduce(-start)?\(", line):
            continue
        result = line.split(" = ", 1)[1].split("all-reduce", 1)[0]
        for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", result):
            total += size[dt] * int(np.prod([int(d) for d in dims.split(",")
                                             if d]))
    return total


def _drain(pool, specs, keys):
    from repro.aqp.query import Query
    qids = [pool.submit(Query(func=f, epsilon=e), key=keys[i])
            for i, (f, e) in enumerate(specs)]
    res = {r.qid: r for r in pool.drain()}
    return [res[qid] for qid in qids]


def _answers(results):
    return [[np.ravel(r.n).tolist(), int(r.iterations), bool(r.success),
             np.asarray(r.error, np.float32).tobytes().hex(),
             np.asarray(r.theta, np.float32).ravel().tobytes().hex()]
            for r in results]


def _held(pool) -> dict:
    """What the pool keeps of the table: the 2-D table and, for each
    column the step reads, whether it is split in the table's row blocks
    over the table's own mesh and holds the table's rows there."""
    from repro.core import mesh as core_mesh

    table = pool.values
    mesh = table.sharding.mesh
    blocks = {s.device: s for s in table.addressable_shards}
    cols = []
    for j, col in enumerate(pool.columns):
        shards = col.addressable_shards
        cols.append({
            "shape": list(col.shape),
            "row_sharded": (
                col.sharding.mesh == mesh
                and col.sharding.is_equivalent_to(
                    core_mesh.data_sharding(mesh), 1)),
            "devices": len({s.device for s in shards}),
            "blocks_of_table": all(
                s.index == blocks[s.device].index[:1]
                and np.array_equal(np.asarray(s.data),
                                   np.asarray(blocks[s.device].data)[:, j])
                for s in shards)})
    return {"shape": list(table.shape),
            "spec": [d for d in table.sharding.spec],
            "columns": cols,
            "by_device": pool.stats()["table_bytes_by_device"]}


def _four_devices() -> dict:
    """The checks on four host devices; a JSON-able summary."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from aqpbench import tpch
    from repro.aqp.query import Query
    from repro.core.sampling import GroupedData
    from repro.serve.lane_pool import LanePool

    assert len(jax.devices()) == SHARDS
    config = {"scale_factor": 1, "group_by": "returnflag",
              "data_shards": SHARDS}
    values, offsets = tpch.make_lineitem(config, 2 ** 33 + 5, rows=ROWS)
    host = np.asarray(jax.device_get(values))[:ROWS]
    avg = [host[offsets[g]:offsets[g + 1], 0].mean() for g in range(3)]
    eps = float(np.linalg.norm(avg))
    specs = [("avg", 0.02 * eps), ("avg", 0.05 * eps),
             ("var", 0.05 * eps ** 2), ("avg", 0.03 * eps),
             ("avg", 0.1 * eps), ("avg", 0.02 * eps)]
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(6), len(specs)))
    kw = dict(lanes=4, tiers=1, data_shards=SHARDS, seed=0,
              sample_key=jax.random.PRNGKey(9), **SPEC)
    out = {"rows_per_shard": -(-ROWS // SHARDS)}

    sharded = GroupedData(values, offsets)
    pool = LanePool(sharded, **kw)
    st = pool.stats()
    out["taken"] = {
        "host_bytes": st["table_host_bytes"],
        "by_device": st["table_bytes_by_device"],
        "same_buffers": (
            {s.device.id: s.data.unsafe_buffer_pointer()
             for s in values.addressable_shards}
            == {s.device.id: s.data.unsafe_buffer_pointer()
                for s in pool.values.addressable_shards})}
    # One tick with the tier busy: the psum count grows by the compiled
    # step's all-reduce bytes, once per tick.
    qid = pool.submit(Query(func="avg", epsilon=0.02 * eps), key=keys[0])
    before = pool.stats()["psum_bytes"]
    pool.tick()
    out["psum"] = {
        "grown": pool.stats()["psum_bytes"] - before,
        "ticks_per_sync": pool.ticks_per_sync,
        "hlo_bytes": _hlo_all_reduce_bytes(
            pool.lowered_tick().compile().as_text())}
    assert [r.qid for r in pool.drain()] == [qid]
    out["held"] = {"taken": _held(pool)}
    before = pool.stats()["psum_bytes"]
    ticks0 = pool.ticks
    out["answers_taken"] = _answers(_drain(pool, specs, keys))
    out["psum"]["drain_grown"] = pool.stats()["psum_bytes"] - before
    out["psum"]["drain_ticks"] = pool.ticks - ticks0

    hosted = GroupedData(host, offsets)
    pool = LanePool(hosted, **kw)
    st = pool.stats()
    out["hosted"] = {"host_bytes": st["table_host_bytes"],
                     "by_device": st["table_bytes_by_device"],
                     "shape": list(pool.values.shape)}
    out["held"]["hosted"] = _held(pool)
    out["answers_hosted"] = _answers(_drain(pool, specs, keys))

    # Held otherwise on the same devices (here: whole on each), the table
    # goes by the host like a host table.
    replicated = GroupedData(jax.device_put(values[:ROWS], NamedSharding(
        Mesh(np.asarray(jax.devices()), ("data",)), PartitionSpec())),
        offsets)
    pool = LanePool(replicated, **kw)
    st = pool.stats()
    out["resharded"] = {"host_bytes": st["table_host_bytes"],
                        "by_device": st["table_bytes_by_device"]}
    out["held"]["resharded"] = _held(pool)
    out["answers_resharded"] = _answers(_drain(pool, specs, keys))
    # A table split over a mesh that orders the devices otherwise than
    # make_data_mesh does (as on a v5e 2x2) is still taken as it is.
    reordered = jax.device_put(values, NamedSharding(
        Mesh(np.asarray(jax.devices()[::-1]), ("data",)),
        PartitionSpec("data", None)))
    pool = LanePool(GroupedData(reordered, offsets), **kw)
    out["reordered"] = {
        "host_bytes": pool.stats()["table_host_bytes"],
        "same_buffers": (
            {s.device.id: s.data.unsafe_buffer_pointer()
             for s in reordered.addressable_shards}
            == {s.device.id: s.data.unsafe_buffer_pointer()
                for s in pool.values.addressable_shards})}
    out["held"]["reordered"] = _held(pool)
    out["answers_reordered"] = _answers(_drain(pool, specs, keys))
    # A mesh that orders the devices the other way round: each block moves
    # to the device that holds its place in the mesh.
    flipped = Mesh(np.asarray(jax.devices()[::-1]), ("data",))
    pool = LanePool(sharded, **{**kw, "mesh": flipped})
    blocks = np.asarray(jax.device_get(values))
    r = out["rows_per_shard"]
    out["flipped"] = {
        "host_bytes": pool.stats()["table_host_bytes"],
        "blocks_in_place": all(
            np.array_equal(np.asarray(s.data),
                           blocks[flipped.devices.tolist().index(s.device)
                                  * r:][:r])
            for s in pool.values.addressable_shards)}
    out["held"]["flipped"] = _held(pool)
    # Two columns: each device holds its block of the table and of each.
    two = np.concatenate([host, 2.0 * host], axis=1)
    out["held"]["two_columns"] = _held(LanePool(GroupedData(two, offsets),
                                                **kw))

    pool = LanePool(hosted, mesh=False, **kw)
    out["one_device"] = {"psum_bytes": pool.stats()["psum_bytes"]}
    out["answers_one_device"] = _answers(_drain(pool, specs, keys))
    return out


@pytest.fixture(scope="module")
def four() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_table_is_taken_as_it_is(four):
    taken = four["taken"]
    assert taken["host_bytes"] == 0
    assert taken["same_buffers"]
    block = four["rows_per_shard"] * 4
    # Its block of the table and of the one column the step reads.
    assert taken["by_device"] == {str(d): 2 * block for d in range(SHARDS)}


def test_table_on_another_device_order_is_taken_as_it_is(four):
    assert four["reordered"] == {"host_bytes": 0, "same_buffers": True}


def test_host_table_is_sent_block_by_block(four):
    hosted = four["hosted"]
    block = four["rows_per_shard"] * 4
    assert hosted["shape"] == [SHARDS * four["rows_per_shard"], 1]
    assert hosted["host_bytes"] == SHARDS * block
    # Each device holds its own block of the table and of the column, and
    # no device more.
    assert hosted["by_device"] == {str(d): 2 * block for d in range(SHARDS)}


def test_table_held_otherwise_is_placed_block_by_block(four):
    block = four["rows_per_shard"] * 4
    assert four["resharded"] == {
        "host_bytes": SHARDS * block,
        "by_device": {str(d): 2 * block for d in range(SHARDS)}}
    assert four["flipped"] == {"host_bytes": 0, "blocks_in_place": True}


@pytest.mark.parametrize("placement", ["taken", "hosted", "resharded",
                                       "reordered", "flipped",
                                       "two_columns"])
def test_columns_are_row_blocks_of_the_table(four, placement):
    """The step reads 1-D columns; ``values`` is still the 2-D table.  Each
    column lies on the table's own mesh, one block a device, and a device's
    block of it is its block of the table's column."""
    held = four["held"][placement]
    r = four["rows_per_shard"]
    c = 2 if placement == "two_columns" else 1
    assert held["shape"] == [SHARDS * r, c]
    assert held["spec"] == ["data", None]
    assert held["columns"] == [{"shape": [SHARDS * r], "row_sharded": True,
                                "devices": SHARDS, "blocks_of_table": True}
                               ] * c
    # table_bytes_by_device counts the table's block and each column's.
    assert held["by_device"] == {str(d): 2 * c * r * 4
                                 for d in range(SHARDS)}


def test_psum_bytes_grow_by_the_payload_per_tick(four):
    from repro.core.fused import sharded_psum_bytes

    psum = four["psum"]
    payload = sharded_psum_bytes(4, 3, SPEC["B"])
    assert psum["hlo_bytes"] == payload
    assert psum["grown"] == payload * psum["ticks_per_sync"]
    assert psum["drain_ticks"] > 0
    assert psum["drain_grown"] == payload * psum["drain_ticks"]
    assert four["one_device"]["psum_bytes"] == 0


@pytest.mark.parametrize("placement", ["answers_taken", "answers_hosted",
                                       "answers_resharded",
                                       "answers_reordered"])
def test_mesh_answers_bit_equal_to_one_device(four, placement):
    assert four[placement] == four["answers_one_device"]


# Group counts (A, N, R) of lineitem at SF 300, as aqpbench/tpch.py made
# them on the chip from seed 5316000113.
SF300_COUNTS = (444_191_988, 911_606_213, 444_190_890)


def test_sf300_layout_fits_int32():
    from repro.core.sampling import ShardLayout

    n = sum(SF300_COUNTS)
    assert n == 1_799_989_091
    offsets = np.concatenate([[0], np.cumsum(SF300_COUNTS)])
    lay = ShardLayout.build(offsets, n_cap=1 << 16, num_shards=SHARDS)
    # aqpbench/tpch.py pads the table to whole shards of the same size.
    assert SHARDS * lay.rows_per_shard == SHARDS * -(-n // SHARDS)
    assert lay.rows_per_shard < 2 ** 31
    # The int32 tables hold what the int64 block partition gives.
    for s in range(SHARDS):
        blo, bhi = s * lay.rows_per_shard, (s + 1) * lay.rows_per_shard
        lo = np.clip(offsets[:-1], blo, bhi)
        hi = np.clip(offsets[1:], blo, bhi)
        np.testing.assert_array_equal(lay.lsizes[s].astype(np.int64),
                                      np.maximum(hi - lo, 0))
        held = hi > lo
        np.testing.assert_array_equal(
            lay.lstarts[s][held].astype(np.int64), (lo - blo)[held])
    assert int(lay.lsizes.astype(np.int64).sum()) == n
    # A and N in shard 0, N in shards 1 and 2, N and R in shard 3.
    np.testing.assert_array_equal(lay.lsizes > 0, [[1, 1, 0], [0, 1, 0],
                                                   [0, 1, 0], [0, 1, 1]])


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    print(json.dumps(_four_devices()), flush=True)
