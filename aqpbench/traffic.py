"""Traffic from a workload file: the query mix, its order and its arrivals.

A workload file (``aqpbench/workloads/<cell>.json``) holds parameters only:

* ``templates``: query shapes, each ``{"func", "base", "rel": [...],
  "group_by", "weight"}``.  The bound is ``rel`` times a base taken from the
  exact answers: ``l2`` (the L2 norm of the func's per-group answers),
  ``avg_l2_sq`` (the squared L2 norm of the group averages, for VAR) or
  ``min_group`` (the smallest group's answer, for GROUP BY).
* ``loop``: ``closed``, with ``clients`` clients each submitting its next
  request once its last is answered.

Every seed gets the same multiset of queries, in its own order: each block
of the stream is one copy of every (template, bound) variant, ``weight``
times over, shuffled.  So a seed changes the order, the bootstrap keys, and
(through the table and the sample it draws) the rows, never the mix.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Tuple

import numpy as np

STREAM_SALT = 0xA9B1


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of the stream."""
    template: int
    func: str
    epsilon: float
    group_by: bool
    key: np.ndarray                # (2,) uint32 bootstrap key


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt])


def variants(workload: Dict, exact: Dict[str, np.ndarray]) -> List[Tuple]:
    """Every ``(template, func, epsilon, group_by)`` variant of the mix."""
    out = []
    for ti, t in enumerate(workload["templates"]):
        func, base = t["func"], t["base"]
        if base == "l2":
            b = float(np.linalg.norm(exact[func]))
        elif base == "avg_l2_sq":
            b = float(np.linalg.norm(exact["avg"])) ** 2
        elif base == "min_group":
            b = float(np.abs(exact[func]).min())
        else:
            raise ValueError(f"unknown bound base {base!r}")
        for rel in t["rel"]:
            out += [(ti, func, rel * b, bool(t.get("group_by", False)))
                    ] * int(t.get("weight", 1))
    return out


def stream(var: List[Tuple], seed: int) -> Iterator[Item]:
    """The request stream: shuffled copies of ``var``, each with its key."""
    rng = _rng(seed, STREAM_SALT)
    while True:
        for i in rng.permutation(len(var)):
            key = rng.integers(0, 2 ** 32, 2, dtype=np.uint64).astype(
                np.uint32)
            yield Item(*var[i], key)
