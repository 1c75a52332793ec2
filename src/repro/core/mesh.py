"""Shared mesh utilities for data-parallel execution (DESIGN.md phase G).

Hoisted from ``aqp/distributed.py`` so the distributed ESTIMATE path and
the sharded lane pool (core/fused.py + serve/lane_pool.py) agree on ONE
mesh construction and ONE row-sharding convention: a 1-D ``("data",)``
mesh, rows padded to a multiple of the device count, ``gid == -1`` (or a
row index past the last group offset) marking padding.

On CPU containers a multi-device mesh is simulated with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` -- which must be in
the environment BEFORE jax is imported (:func:`host_device_flag` builds
the flag string; benchmarks/run.py ``--devices`` and the CI multi-device
job both use it).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Array = jax.Array

# The one data-parallel axis name every sharded component agrees on.
DATA_AXIS = "data"


def host_device_flag(n: int) -> str:
    """The XLA flag forcing ``n`` simulated host devices.

    Must be placed in ``XLA_FLAGS`` BEFORE the first ``import jax`` --
    appending after jax initialized its backend has no effect.
    """
    return f"--xla_force_host_platform_device_count={int(n)}"


def make_data_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D ``("data",)`` mesh over ``num_devices`` devices (default: all)."""
    devs = jax.devices()
    if num_devices is None or int(num_devices) == len(devs):
        return jax.make_mesh((len(devs),), (DATA_AXIS,))
    n = int(num_devices)
    if n > len(devs):
        raise ValueError(
            f"requested a {n}-device data mesh but only {len(devs)} "
            f"device(s) are visible; set XLA_FLAGS="
            f"{host_device_flag(n)!r} before importing jax")
    return Mesh(np.asarray(devs[:n]), (DATA_AXIS,))


def table_mesh(values, num_devices: int) -> Mesh:
    """The data mesh of a table held in ``num_devices`` row shards: the one
    ``values`` is already split along rows over, if it is such a mesh, so
    that placing the table moves nothing; else :func:`make_data_mesh`'s
    (whose device order may differ: on a v5e 2x2 it is [0, 1, 3, 2])."""
    sh = getattr(values, "sharding", None)
    if (isinstance(sh, NamedSharding) and sh.mesh.axis_names == (DATA_AXIS,)
            and sh.mesh.devices.size == int(num_devices)
            and tuple(sh.spec)[:1] == (DATA_AXIS,)):
        return sh.mesh
    return make_data_mesh(num_devices)


def data_sharding(mesh: Mesh, ndim: int = 1, axis: int = 0) -> NamedSharding:
    """Sharding with dimension ``axis`` split over the data axis, the rest
    replicated."""
    spec = [None] * int(ndim)
    spec[axis] = DATA_AXIS
    return NamedSharding(mesh, P(*spec))


def put_sharded(mesh: Mesh, x, axis: int = 0) -> Array:
    """``device_put`` with dimension ``axis`` sharded over the data axis.

    Host input goes straight to the sharding, so each device receives its
    own slice alone; a device array moves shard by shard.  Neither is
    first committed whole to one device."""
    if not isinstance(x, jax.Array):
        x = np.asarray(x)
    return jax.device_put(x, data_sharding(mesh, x.ndim, axis))


def put_row_blocks(mesh: Mesh, values, rows: int) -> Tuple[Array, int]:
    """``(table, host_bytes)``: the 2-D table row-sharded over the mesh in
    whole blocks of ``rows / mesh size`` rows, zero-padded to ``rows``.

    A device array that already holds ``rows`` rows split along rows over
    the mesh's devices is taken as it is (a ``device_put`` to the mesh's
    sharding: nothing moves, or each shard moves between two devices where
    the mesh orders them differently) and ``host_bytes`` is 0.  Anything
    else is padded on the host and sent slice by slice (``host_bytes`` is
    the padded table's size).  No device ever holds more than its block.
    """
    sharding = data_sharding(mesh, 2)
    if (isinstance(values, jax.Array) and values.ndim == 2
            and values.shape[0] == rows
            and values.sharding.device_set == set(mesh.devices.flat)
            and values.sharding.shard_shape(values.shape)
            == (rows // mesh.devices.size, values.shape[1])):
        return jax.device_put(values, sharding), 0
    v = pad_rows(values, rows)
    return jax.device_put(v, sharding), int(v.nbytes)


def pad_rows(values, rows: int) -> np.ndarray:
    """The table on the host, 2-D, zero-padded to ``rows`` rows."""
    v = np.asarray(values)
    if v.ndim == 1:
        v = v[:, None]
    if len(v) < rows:
        v = np.pad(v, ((0, rows - len(v)), (0, 0)))
    return v


def put_replicated(mesh: Mesh, x) -> Array:
    """``device_put`` fully replicated over the mesh."""
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))


def shard_dataset(mesh: Mesh, gid: np.ndarray, x: np.ndarray):
    """Places ``(gid, x)`` row-sharded over the mesh's data axis.

    Rows are padded to a multiple of the device count with ``gid == -1``
    marking invalid (padding) rows -- the convention every sharded consumer
    (aqp/distributed.py, the sharded ESTIMATE masking tests) relies on.
    """
    sh = NamedSharding(mesh, P(DATA_AXIS))
    n = len(gid)
    per = -(-n // mesh.devices.size)
    pad = per * mesh.devices.size - n
    gid_p = np.pad(gid, (0, pad), constant_values=-1)   # -1 = invalid row
    x_p = np.pad(x, (0, pad))
    return (jax.device_put(gid_p.astype(np.int32), sh),
            jax.device_put(x_p.astype(np.float32), sh))
