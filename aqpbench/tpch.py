"""TPC-H ``lineitem`` made on the device from a seed, and its exact answers.

The columns follow the TPC-H specification (v3, clause 4.2.3):

* ``L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE`` with ``L_QUANTITY`` in
  U[1, 50], ``L_PARTKEY`` in U[1, SF * 200,000] and
  ``P_RETAILPRICE = (90000 + ((PARTKEY / 10) mod 20001) + 100 * (PARTKEY mod
  1000)) / 100``;
* ``L_RETURNFLAG``: ``O_ORDERDATE`` in U[STARTDATE, ENDDATE - 151 days],
  ``L_SHIPDATE = O_ORDERDATE + U[1, 121]``, ``L_RECEIPTDATE = L_SHIPDATE +
  U[1, 30]``; 'N' when the receipt date is after CURRENTDATE (1995-06-17),
  else 'R' or 'A' with equal probability;
* ``L_LINENUMBER``: each order has U[1, 7] lines, so a line's number is k
  with probability (8 - k) / 28.

The price does not depend on the group column, so the group-sorted table
(the layout ``GroupedData`` serves) is the price column in draw order cut
into runs of the per-group counts.  Everything is made in one jitted call;
only the counts come back to the host.  A configuration with
``"data_shards": S`` (S > 1) gets the table row-sharded over the first S
devices, made there shard by shard, so that no device holds it whole.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Days from STARTDATE (1992-01-01).
ORDERDATE_MAX = 2405          # ENDDATE (1998-12-31) - 151 days
CURRENTDATE = 1263            # 1995-06-17
LINENUMBER_CUM = (7, 13, 18, 22, 25, 27, 28)   # sum of (8 - k) for k <= K


def seed_key(seed: int):
    """A JAX key for any non-negative seed up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, rows: int, scale_factor: int, group_by: str):
    """``(price (rows,) float32, gid (rows,) int32, m)``: the rows in draw
    order and the group of each."""
    k_part, k_qty, k_g1, k_g2, k_g3 = jax.random.split(key, 5)
    partkey = jax.random.randint(k_part, (rows,), 1,
                                 scale_factor * 200_000 + 1, jnp.int32)
    retail_cents = (90_000 + (partkey // 10) % 20_001
                    + 100 * (partkey % 1000))
    qty = jax.random.randint(k_qty, (rows,), 1, 51, jnp.int32)
    price = (qty * retail_cents).astype(jnp.float32) / jnp.float32(100.0)
    if group_by == "returnflag":
        order = jax.random.randint(k_g1, (rows,), 0, ORDERDATE_MAX + 1,
                                   jnp.int32)
        k_ship, k_recv = jax.random.split(k_g2)
        receipt = (order
                   + jax.random.randint(k_ship, (rows,), 1, 122, jnp.int32)
                   + jax.random.randint(k_recv, (rows,), 1, 31, jnp.int32))
        coin = jax.random.bernoulli(k_g3, 0.5, (rows,))
        # Groups in sort order of the flag: A, N, R.
        gid = jnp.where(receipt > CURRENTDATE, 1, jnp.where(coin, 0, 2))
        m = 3
    elif group_by == "linenumber":
        u = jax.random.randint(k_g1, (rows,), 0, 28, jnp.int32)
        gid = jnp.sum(u[:, None] >= jnp.asarray(LINENUMBER_CUM[:-1]),
                      axis=1)
        m = 7
    else:
        raise ValueError(f"unknown group column {group_by!r}")
    return price, gid, m


def _counts(gid, m: int):
    return jnp.stack([jnp.sum(gid == g, dtype=jnp.int32) for g in range(m)])


@functools.partial(jax.jit,
                   static_argnames=("rows", "scale_factor", "group_by"))
def _make(key, *, rows: int, scale_factor: int, group_by: str):
    price, gid, m = _draw(key, rows, scale_factor, group_by)
    return price[:, None], _counts(gid, m)


def _make_padded(key, *, rows: int, padded: int, scale_factor: int,
                 group_by: str):
    """``_make`` drawn over ``padded >= rows`` rows, the ``padded - rows``
    rows at the end zero and in no group."""
    price, gid, m = _draw(key, padded, scale_factor, group_by)
    valid = jnp.arange(padded) < rows
    price = jnp.where(valid, price, jnp.float32(0.0))
    return price[:, None], _counts(jnp.where(valid, gid, m), m)


def make_lineitem(config: Dict, seed: int, rows: int | None = None
                  ) -> Tuple[object, np.ndarray]:
    """``(values (N', 1) float32 on the device, offsets (m + 1,) int64)``.

    With ``"data_shards": S`` above 1 the table is made row-sharded over
    the first S devices (a 1-D ``("data",)`` mesh), padded with zero rows
    to ``N' = S * ceil(N / S)``, whole shards; the offsets cover the N rows
    of the groups alone.  Otherwise ``N' = N``, on the default device.
    """
    n = int(rows if rows is not None else config["rows"])
    shards = int(config.get("data_shards", 1))
    kw = dict(scale_factor=int(config["scale_factor"]),
              group_by=config["group_by"])
    if shards == 1:
        values, counts = _make(seed_key(seed), rows=n, **kw)
    else:
        mesh = Mesh(np.asarray(jax.devices()[:shards]), ("data",))
        make = jax.jit(
            _make_padded,
            static_argnames=("rows", "padded", "scale_factor", "group_by"),
            out_shardings=(NamedSharding(mesh, PartitionSpec("data", None)),
                           NamedSharding(mesh, PartitionSpec())))
        values, counts = make(seed_key(seed), rows=n,
                              padded=shards * -(-n // shards), **kw)
    counts = np.asarray(jax.device_get(counts), np.int64)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    return values, offsets


def exact_answers(vals: np.ndarray, offsets: np.ndarray) -> Dict[str, np.ndarray]:
    """Per-group float64 full-scan answers of the moment family.

    SUM and COUNT report ``|D_g|`` times the group mean (paper SS2.2.1): a
    COUNT without a predicate over the price column is its sum.  VAR is the
    population variance, as the estimator defines it.
    """
    out = {k: [] for k in ("avg", "sum", "count", "var", "size")}
    for g in range(len(offsets) - 1):
        x = vals[offsets[g]:offsets[g + 1]].astype(np.float64)
        mu = x.mean()
        out["avg"].append(mu)
        out["sum"].append(x.sum())
        out["count"].append(x.sum())
        out["var"].append(np.mean((x - mu) ** 2))
        out["size"].append(float(len(x)))
    return {k: np.asarray(v) for k, v in out.items()}
