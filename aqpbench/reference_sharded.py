"""Plain reference for the answers of a row-sharded lane pool.

A pool with ``data_shards = S > 1`` (DESIGN.md SS7 phase G) draws its
sample and its replicate weights differently from the one-shard pool that
``aqpbench/reference.py`` follows.  As the design documents it:

* the table is cut into S row blocks of ``rows_per_shard = ceil(N / S)``
  rows; group g's extent meets block s in at most one sub-extent, of
  ``z[s, g]`` rows;
* a lane buffer's slot axis is cut into S segments of ``seg_cap = n_cap /
  S`` slots.  Logical slots of group g go to shards by a proportional-
  emission merge: shard s (where ``z[s, g] > 0``) emits the times ``k * (Z_g
  / z[s, g])`` for ``k = 1..seg_cap``, ``Z_g`` the group's rows; the times
  are merged in order of ``(time, shard)``, and the first ``n_cap`` of
  them are the group's logical slots.  ``alloc[g, s, n]`` counts the
  shard-s slots among the first n, and logical slot i is segment slot
  ``alloc[g, s, i]`` of its shard s.  Group g holds ``cap[g] = max(1,
  min(merged slots, |D_g|))`` slots;
* segment slot j of shard s reads row ``start[s, g] + floor(u * z[s, g])``
  of the shard's sub-extent, with ``u`` hashed from ``(slot seed, g, s *
  seg_cap + j)``: the buffer-global slot id;
* a tick grows a segment by at most ``seg_window`` slots: the logical
  watermark grows only as far as every shard's share fits that window;
* replicate b weighs segment slot j of shard s by ``Poisson1(hash(seed_gs,
  j, b))``, ``seed_gs = hash(seed_g(k), s, SHARD_SALT)``: the absolute
  segment-local position, whatever window the tick reads;
* the shards' raw moment sums are added (a ``psum``), and the dead-replicate
  guard and the finish run on the sum.

``ShardedReference`` rebuilds all of that from the table, the offsets, the
session's seed and the pool's recorded ``TRAJECTORY_SHARDED``, with nothing
of the program imported; ``Reference``'s search then finds the epoch, the
window and the tick and adds the shards' sums in float64.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from aqpbench.reference import (
    SALT_GROUP, SALT_SLOT, Reference, fold_in, hash3, key_bits, slot_rows)

SHARD_SALT = 0x5DA7


class ShardedReference(Reference):
    """Recomputes the answers of a pool of ``data_shards`` row shards."""

    def __init__(self, values: np.ndarray, offsets: np.ndarray, *,
                 session_seed: int, B: int, n_min: int, n_max: int,
                 n_cap: int, max_iters: int, l: int, seg_window: int,
                 data_shards: int):
        super().__init__(values, offsets, session_seed=session_seed, B=B,
                         n_min=n_min, n_max=n_max, n_cap=n_cap,
                         max_iters=max_iters, l=l, ext_cap=0)
        S = self.S = int(data_shards)
        self.seg_window = int(seg_window)
        self.seg_cap = n_cap // S
        R = self.rows_per_shard = -(-max(int(self.offsets[-1]), 1) // S)
        block = np.arange(S)[:, None] * R
        lo = np.clip(self.offsets[None, :-1], block, block + R)
        hi = np.clip(self.offsets[None, 1:], block, block + R)
        self.sub_start, self.sub_size = lo, np.maximum(hi - lo, 0)  # (S, m)
        self.alloc = np.zeros((self.m, S, n_cap + 1), np.int64)
        self.shard_of: List[np.ndarray] = []     # shard of each logical slot
        cap = []
        for g in range(self.m):
            z = self.sub_size[:, g].astype(np.float64)
            k = np.arange(1, self.seg_cap + 1, dtype=np.float64)
            held = [s for s in range(S) if z[s] > 0]
            times = np.concatenate([k * (z.sum() / z[s]) for s in held]
                                   or [np.zeros(0)])
            shard = np.concatenate([np.full(self.seg_cap, s) for s in held]
                                   or [np.zeros(0, np.int64)])
            shard = shard[np.lexsort((shard, times))][:n_cap]
            owned = np.cumsum(shard[None, :] == np.arange(S)[:, None], 1)
            self.alloc[g, :, 1:len(shard) + 1] = owned
            self.alloc[g, :, len(shard) + 1:] = owned[:, -1:] if len(
                shard) else 0
            self.shard_of.append(shard)
            cap.append(max(min(len(shard), int(self.sizes[g])), 1))
        self.cap = np.asarray(cap, np.int64)
        self._tables: Dict[Tuple[int, int], np.ndarray] = {}

    # -- layout
    def shard_rows(self, filled: np.ndarray) -> np.ndarray:
        """(S,) segment slots held at logical watermarks ``filled`` (m,)."""
        f = np.minimum(np.asarray(filled, np.int64), self.n_cap)
        return self.alloc[np.arange(self.m), :, f].sum(0)

    def headroom(self, filled: np.ndarray) -> np.ndarray:
        """(m,) logical growth past ``filled`` that every shard's segment
        takes within one tick's ``seg_window``."""
        out = []
        for g, f in enumerate(filled):
            hi = [np.searchsorted(a, a[f] + self.seg_window, side="right") - 1
                  for a in self.alloc[g]]
            out.append(min(hi) - f)
        return np.asarray(out, np.int64)

    # -- sample binding
    def tables(self, epoch: int, g: int) -> np.ndarray:
        """(S, seg_cap) table rows of every segment slot of group ``g``."""
        key = (epoch, g)
        if key not in self._tables:
            seed = key_bits(fold_in(self._root, epoch), SALT_SLOT)
            tab = np.zeros((self.S, self.seg_cap), np.int64)
            for s in range(self.S):
                if self.sub_size[s, g] > 0:
                    tab[s] = slot_rows(seed, g, int(self.sub_start[s, g]),
                                       int(self.sub_size[s, g]),
                                       self.seg_cap, first=s * self.seg_cap)
            self._tables[key] = tab
        return self._tables[key]

    def rows(self, epoch: int, grouped: bool, g: int) -> np.ndarray:
        """Table rows of group ``g``'s logical slots in sample epoch
        ``epoch``."""
        if grouped:
            raise ValueError("a sharded pool serves no grouped lane block")
        shard = self.shard_of[g][:self.cap[g]]
        local = self.alloc[g, shard, np.arange(len(shard))]
        return self.tables(epoch, g)[shard, local]

    # -- windows
    def window(self, k: int, n: np.ndarray, group_ids: Sequence[int]
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        if k >= self.l:
            return np.zeros_like(n), n.copy()
        m = len(n)
        cap = self.cap[list(group_ids)]
        filled = np.zeros(m, np.int64)
        for t in range(k + 1):
            phase = (t + np.arange(m)) % self.l
            nt = np.where(phase < self.l_min, self.n_min, self.n_max)
            nt = np.minimum(np.clip(nt, 1, cap), self.headroom(filled))
            lo = np.minimum(filled, cap - nt)
            hi = lo + nt
            filled = np.maximum(filled, hi)
        return (lo, hi) if np.array_equal(hi - lo, n) else None

    # -- the window's parts and their bootstrap seeds
    def parts(self, epoch: int, grouped: bool, group_ids: Sequence[int],
              lo: np.ndarray, hi: np.ndarray
              ) -> List[List[Tuple[np.ndarray, int, int]]]:
        """Per group, its window's part on each shard: the values of
        segment slots ``[a, b)``, the shard's share of logical ``[lo, hi)``."""
        if grouped:
            raise ValueError("a sharded pool serves no grouped lane block")
        out = []
        for g, lo_g, hi_g in zip(group_ids, lo, hi):
            tab = self.tables(epoch, g)
            out.append([])
            for sh in range(self.S):
                a = int(self.alloc[g, sh, lo_g])
                b = int(self.alloc[g, sh, hi_g])
                out[-1].append((self.values[tab[sh, a:b]], a, b))
        return out

    def seeds(self, bases: Sequence[int], k: int, grouped: bool,
              group_ids: Sequence[int]) -> List[List[int]]:
        """``(m, S)`` bootstrap seeds of each group's part on each shard at
        tick ``k``."""
        return [[int(hash3(hash3(hash3(b, k, SALT_GROUP), g, SALT_GROUP),
                           sh, SHARD_SALT)) for sh in range(self.S)]
                for g, b in zip(group_ids, bases)]
