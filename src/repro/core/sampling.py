"""Sampling substrate: grouped datasets, stratified sampling, two-point init,
and the incremental ``SampleStore`` (permuted-prefix sampling).

The paper avoids full scans with (i) gap sampling and (ii) an inverted index
on the group-by attributes (SS4.1).  The TPU-idiomatic analogue (DESIGN.md SS3):
the dataset lives *sorted by group* with an offset table -- the dense inverted
index -- and per-group sampling draws uniform indices into each group's
contiguous extent.  Only the sampled rows are ever touched.

All device-side sampling is fixed-shape: groups are padded to a common cap and
masked, so the same jitted program serves every MISS iteration in a size
bucket (see l2miss.py bucketing).

``SampleStore`` (DESIGN.md SS3.2) makes sampling *incremental*: each group
holds a lazily-materialized uniform random permutation of its extent, and "a
sample of size n" is defined as the first n entries of that permutation.
Growing n -> n + delta therefore gathers only delta new rows, samples are
nested across MISS iterations, and the same prefixes can be shared across
queries (one resident store per dataset in serve/aqp_service.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Grouped dataset = sorted-by-group values + offset table (inverted index)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GroupedData:
    """A dataset pre-partitioned by the GROUP BY attribute.

    values:  (N, c) rows, sorted so each group occupies a contiguous extent.
    offsets: (m + 1,) int64 group boundaries into ``values``.
    scale:   (m,) per-group population scale |D|_i used by SUM/COUNT
             (paper SS2.2.1 transformation); defaults to group sizes.
    """

    values: Array
    offsets: np.ndarray
    scale: Optional[np.ndarray] = None

    def __post_init__(self):
        self.values = jnp.asarray(self.values)
        if self.values.ndim == 1:
            self.values = self.values[:, None]
        self.offsets = np.asarray(self.offsets, dtype=np.int64)
        if self.scale is None:
            self.scale = self.sizes.astype(np.float64)

    @property
    def num_groups(self) -> int:
        return len(self.offsets) - 1

    @property
    def sizes(self) -> np.ndarray:
        return np.diff(self.offsets)

    @property
    def num_columns(self) -> int:
        return self.values.shape[1]

    @staticmethod
    def from_columns(group_ids, values) -> "GroupedData":
        """Build from unsorted (group_id, value) columns -- the 'index build'."""
        group_ids = np.asarray(group_ids)
        values = np.asarray(values)
        if values.ndim == 1:
            values = values[:, None]
        order = np.argsort(group_ids, kind="stable")
        gid_sorted = group_ids[order]
        m = int(gid_sorted[-1]) + 1 if len(gid_sorted) else 0
        offsets = np.searchsorted(gid_sorted, np.arange(m + 1))
        return GroupedData(jnp.asarray(values[order]), offsets)

    @staticmethod
    def from_group_arrays(groups: Sequence[np.ndarray]) -> "GroupedData":
        arrs = [np.asarray(g) for g in groups]
        arrs = [a[:, None] if a.ndim == 1 else a for a in arrs]
        offsets = np.concatenate([[0], np.cumsum([len(a) for a in arrs])])
        return GroupedData(jnp.asarray(np.concatenate(arrs, axis=0)), offsets)


# ---------------------------------------------------------------------------
# PRNG root
# ---------------------------------------------------------------------------

def root_key(seed: int):
    """The sanctioned constructor for a fresh PRNG stream root.

    Bit-identical to ``jax.random.PRNGKey(seed)`` -- every repeatability
    claim in the repo (counter-slot tables, bootstrap parity, warm-start
    signatures) rests on keys rooted here or at the audited session/pool
    init sites; misslint ML201 flags any other construction site.  Derive
    substreams with ``jax.random.split`` / ``fold_in``, never a new root.
    """
    return jax.random.PRNGKey(seed)


# ---------------------------------------------------------------------------
# Stratified uniform sampling (device-side, fixed shape, masked)
# ---------------------------------------------------------------------------

def stratified_sample(
    key: Array,
    values: Array,
    offsets: Array,
    n_vec: Array,
    n_cap: int,
) -> Tuple[Array, Array]:
    """Draw ``n_vec[i]`` uniform rows from each group's extent.

    Returns ``(sample (m, n_cap, c), mask (m, n_cap))``.  Draws are with
    replacement -- statistically identical to iid draws from each group's
    empirical distribution, which is what the bootstrap theory assumes, and
    gather-free shape-wise (a single fancy-index per group row block).
    """
    m = offsets.shape[0] - 1
    starts = offsets[:-1]
    sizes = offsets[1:] - offsets[:-1]
    u = jax.random.uniform(key, (m, n_cap))
    idx = starts[:, None] + jnp.minimum(
        (u * sizes[:, None]).astype(jnp.int32), (sizes[:, None] - 1).astype(jnp.int32)
    )
    sample = values[idx]  # (m, n_cap, c)
    mask = (jnp.arange(n_cap)[None, :] < n_vec[:, None]).astype(jnp.float32)
    return sample, mask


def stratified_sample_host(
    rng: np.random.Generator, data: GroupedData, n_vec: np.ndarray, n_cap: int
) -> Tuple[Array, Array]:
    """Host-side variant (numpy RNG) used by the reference/benchmark path."""
    m = data.num_groups
    idx = np.zeros((m, n_cap), dtype=np.int64)
    mask = np.zeros((m, n_cap), dtype=np.float32)
    sizes = data.sizes
    for i in range(m):
        k = int(min(n_vec[i], n_cap))
        idx[i, :k] = data.offsets[i] + rng.integers(0, sizes[i], size=k)
        mask[i, :k] = 1.0
    return jnp.asarray(np.asarray(data.values)[idx]), jnp.asarray(mask)


# ---------------------------------------------------------------------------
# Two-point initialization (paper SS4.4, Eq. 17)
# ---------------------------------------------------------------------------

def two_point_init_sizes(
    key, m: int, l: int, n_min: int, n_max: int
) -> np.ndarray:
    """Initial l x m sample-size matrix from the Bhatia-Davis optimal design.

    Paper Eq. 15/16: of the l probes per group, l_max/l_min = n_min/n_max,
    i.e. a fraction n_max/(n_min+n_max) of entries sit at n_min and the rest
    at n_max -- this minimizes (E N)^2 / D N and hence the WLS MSE (SS4.4).
    We allocate the counts deterministically (clamped so both design points
    appear at least once -- a constant column makes the slope unidentifiable)
    and shuffle each column independently.
    """
    l_min = int(round(l * n_max / (n_min + n_max)))
    l_min = min(max(l_min, 1), l - 1)
    col = np.concatenate([
        np.full((l_min,), n_min, np.int64),
        np.full((l - l_min,), n_max, np.int64),
    ])
    sizes = np.tile(col[:, None], (1, m))
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key)).ravel()[-1])
    for j in range(m):
        rng.shuffle(sizes[:, j])
    return sizes


# ---------------------------------------------------------------------------
# Gap sampling (paper SS4.1, [Erlandson 2014]) -- host-side reference
# ---------------------------------------------------------------------------

def gap_sample_indices(rng: np.random.Generator, n_rows: int, p: float) -> np.ndarray:
    """Bernoulli(p) row subset without touching every row.

    Gaps between successive kept rows are Geometric(p); we jump by the gap
    instead of flipping a coin per row.  Kept for paper fidelity and used by
    the CPU AQP path; the TPU path uses stratified_sample (DESIGN.md SS3).
    """
    if p <= 0.0:
        return np.empty((0,), dtype=np.int64)
    if p >= 1.0:
        return np.arange(n_rows, dtype=np.int64)
    # E[#kept] = n*p; oversample the geometric draws and trim.
    est = int(n_rows * p + 10 * np.sqrt(n_rows * p + 1)) + 16
    gaps = rng.geometric(p, size=est)
    pos = np.cumsum(gaps) - 1
    return pos[pos < n_rows].astype(np.int64)


# ---------------------------------------------------------------------------
# Counter-PRNG slot binding (the fused-loop analogue of _PrefixPermutation)
# ---------------------------------------------------------------------------

# Domain-separation salt for the slot->row stream.  Shared by core/fused.py
# and serve/lane_pool.py so one ``sample_key`` names one binding everywhere.
SLOT_SALT = 0x5A17


def counter_slot_table(sample_key, starts, sizes, n_cap: int):
    """(m, n_cap) slot->row binding: slot j of group i reads a fixed row.

    Row = ``start_i + floor(u * size_i)`` with ``u`` a murmur3 counter hash
    of ``(seed, i, j)`` (`kernels/prng.hash3`), so the sample sequence is a
    pure function of the key: iteration k+1's sample extends iteration k's
    prefix, and two programs given the same key gather the same rows (the
    serve-layer shared-prefix contract).  Computing the table is elementwise
    integer work -- no data rows are touched until a gather reads them.
    """
    from ..kernels import prng

    starts = jnp.asarray(starts, jnp.int32)
    sizes = jnp.asarray(sizes, jnp.int32)
    m = sizes.shape[0]
    seed = jax.random.bits(
        jax.random.fold_in(sample_key, SLOT_SALT), (), jnp.uint32)
    rows_i = jnp.arange(m, dtype=jnp.uint32)[:, None]
    cols_j = jnp.arange(n_cap, dtype=jnp.uint32)[None, :]
    u = prng.uniform01(prng.hash3(seed, rows_i, cols_j))       # (m, n_cap)
    return starts[:, None] + jnp.minimum(
        (u * sizes[:, None]).astype(jnp.int32), sizes[:, None] - 1)


def stratum_key(sample_key, g):
    """The per-stratum sample key of group ``g`` under a shared binding.

    Grouped lane blocks (DESIGN.md phase I) give every group its OWN
    counter-PRNG slot->row stream by folding the group index into the
    shared ``sample_key``.  This is the parity anchor for per-group
    verification: a block lane bound to group g draws exactly the rows a
    SOLO run over group g's slice would draw when that run is seeded with
    ``stratum_key(sample_key, g)`` -- same key, same stream, same rows
    (shifted by the group's start offset).
    """
    return jax.random.fold_in(sample_key, g)


def stratified_slot_tables(sample_key, offsets, n_cap: int):
    """(G, 1, n_cap) per-stratum slot->row bindings (BlinkDB-style).

    Stratified analogue of :func:`counter_slot_table` for a grouped lane
    block: table ``g`` binds the block lane of group g -- slot j reads row
    ``start_g + floor(u * size_g)`` with ``u`` hashed from
    ``stratum_key(sample_key, g)``'s stream.  Each stratum therefore grows
    its own nested permuted prefix: rare groups extend their own prefixes
    instead of starving under uniform sampling, and the first k columns of
    a stratum's table are identical at ANY capacity >= k (the nested-prefix
    guarantee the fused loop's carried buffer relies on).

    The middle axis is the lane-local group axis (m = 1): the result plugs
    directly into ``LaneParams.slot_idx`` as a per-lane binding.
    """
    offsets = jnp.asarray(offsets)
    starts = offsets[:-1].astype(jnp.int32)
    sizes = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    G = starts.shape[0]

    def one(g, st, sz):
        return counter_slot_table(
            stratum_key(sample_key, g), st[None], sz[None], n_cap)

    return jax.vmap(one)(jnp.arange(G), starts, sizes)


def bucket_cap(n: int, *, base: int = 256) -> int:
    """Round ``n`` up to the next power-of-two bucket >= base.

    MISS resizes the sample every iteration; bucketing the padded cap keeps
    the number of distinct jit signatures logarithmic in the final size.
    """
    cap = base
    while cap < n:
        cap *= 2
    return cap


# ---------------------------------------------------------------------------
# Sharded slot binding: the counter-PRNG binding split over row shards
# (DESIGN.md phase G)
# ---------------------------------------------------------------------------

# Domain-separation salt folding the shard index into the per-segment
# bootstrap seed stream (core/fused.py `_sharded_step_body`).
SHARD_SALT = 0x5DA7


def _shard_alloc_tables(lsizes: np.ndarray, n_cap: int,
                        cap_s: int) -> Tuple[np.ndarray, np.ndarray]:
    """Cumulative slot-ownership tables for a sharded group layout.

    ``lsizes[s, i]`` is how many rows of group i live on shard s.  Logical
    sample slots of group i are assigned to shards by a deterministic
    proportional-emission merge: shard s emits candidate "times"
    ``k * (Z_i / z_si)`` for ``k = 1..cap_s`` (``Z_i`` the group's total
    rows), candidates are merged by ``(time, shard)`` lexsort, and the first
    ``n_cap`` merged candidates are the group's logical slot order.  The
    returned ``alloc[s, i, n]`` counts how many of the first ``n`` logical
    slots shard s owns.

    Properties the fused step relies on:

    * *identity at S=1*: one shard emits times ``k * 1`` so
      ``alloc[0, i, n] == min(n, cap_groups[i])``.
    * *1-Lipschitz*: ``alloc[s, i, n+1] - alloc[s, i, n] in {0, 1}``, so
      ``inv_alloc(alloc(f) + W) >= f + W`` -- one tick's growth clamp
      (core/fused.py) always grants at least the static per-segment gather
      window.
    * *proportional*: shard s owns ~``z_si / Z_i`` of the slots, matching
      the stratified-over-shards semantics of
      ``aqp.distributed.sharded_bootstrap_estimate``.
    """
    S, m = lsizes.shape
    alloc = np.zeros((S, m, n_cap + 1), np.int64)
    cap_groups = np.zeros((m,), np.int64)
    for i in range(m):
        z = lsizes[:, i].astype(np.float64)
        total = z.sum()
        if total <= 0:
            continue
        times: List[np.ndarray] = []
        sids: List[np.ndarray] = []
        k = np.arange(1, cap_s + 1, dtype=np.float64)
        for s in range(S):
            if z[s] <= 0:
                continue
            times.append(k * (total / z[s]))
            sids.append(np.full(cap_s, s, np.int64))
        t = np.concatenate(times)
        sid = np.concatenate(sids)
        order = np.lexsort((sid, t))          # stable: ties break by shard id
        sid = sid[order][:n_cap]
        cap_groups[i] = len(sid)
        for s in range(S):
            owned = np.cumsum(sid == s)
            alloc[s, i, 1:1 + len(sid)] = owned
            alloc[s, i, 1 + len(sid):] = owned[-1] if len(sid) else 0
    return alloc, cap_groups


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """Host-side description of a grouped table split into S row blocks.

    Rows are block-partitioned: shard s owns rows ``[s*R, (s+1)*R)`` of the
    (padded) table, ``R = rows_per_shard``.  Each group's contiguous extent
    intersects each block in at most one sub-extent (``lstarts``/``lsizes``,
    shard-local offsets).  The fused lane buffer's slot axis is likewise
    segmented into S contiguous segments of ``seg_cap = n_cap // S`` slots,
    and ``alloc`` maps logical sample-prefix lengths to per-segment fills
    (see :func:`_shard_alloc_tables`).  ``cap_groups[i]`` is group i's total
    logical slot capacity (<= n_cap; also clamped to the group size to match
    the solo step's ``n <= size`` clip).
    """
    num_shards: int
    rows_per_shard: int
    n_cap: int
    lstarts: np.ndarray     # (S, m) int32, shard-local row starts
    lsizes: np.ndarray      # (S, m) int32
    alloc: np.ndarray       # (S, m, n_cap + 1) int32, cumulative ownership
    cap_groups: np.ndarray  # (m,) int32

    @property
    def seg_cap(self) -> int:
        return self.n_cap // self.num_shards

    @staticmethod
    def build(offsets, *, n_cap: int, num_shards: int) -> "ShardLayout":
        offsets = np.asarray(offsets, np.int64)
        S = int(num_shards)
        if S < 1:
            raise ValueError(f"num_shards must be >= 1; got {S}")
        if n_cap % S:
            raise ValueError(f"n_cap={n_cap} must divide by num_shards={S}")
        n_rows = int(offsets[-1])
        rows_per_shard = -(-max(n_rows, 1) // S)
        m = len(offsets) - 1
        lstarts = np.zeros((S, m), np.int64)
        lsizes = np.zeros((S, m), np.int64)
        for s in range(S):
            blo = s * rows_per_shard
            bhi = blo + rows_per_shard
            lo = np.clip(offsets[:-1], blo, bhi)
            hi = np.clip(offsets[1:], blo, bhi)
            lsizes[s] = np.maximum(hi - lo, 0)
            # Clamp empty sub-extents to a valid local row so slot tables
            # stay in-bounds (their slots are never gathered: alloc owns 0).
            lstarts[s] = np.where(lsizes[s] > 0, lo - blo, 0)
        alloc, cap_groups = _shard_alloc_tables(lsizes, n_cap, n_cap // S)
        cap_groups = np.minimum(cap_groups, np.diff(offsets))
        cap_groups = np.maximum(cap_groups, 1)      # keep n >= 1 clips valid
        return ShardLayout(
            num_shards=S, rows_per_shard=int(rows_per_shard), n_cap=int(n_cap),
            lstarts=lstarts.astype(np.int32), lsizes=lsizes.astype(np.int32),
            alloc=alloc.astype(np.int32), cap_groups=cap_groups.astype(np.int32))

    # -- host-side helpers ---------------------------------------------------
    def shard_rows(self, filled) -> np.ndarray:
        """(S,) resident slots per shard at per-group watermarks ``filled``
        (m,) -- the per-shard dispatch accounting the pool's stats report."""
        f = np.minimum(np.asarray(filled, np.int64).reshape(-1), self.n_cap)
        gi = np.arange(self.alloc.shape[1])
        return np.stack([self.alloc[s, gi, f].sum()
                         for s in range(self.num_shards)])

    def max_shard_frac(self) -> float:
        """Largest per-shard share of any group's rows (cost-model scalar:
        translates a global watermark into a worst-case segment fill)."""
        z = self.lsizes.astype(np.float64)
        tot = np.maximum(z.sum(axis=0), 1.0)
        return float((z / tot[None, :]).max()) if z.size else 1.0


def sharded_slot_tables(sample_key, layout: ShardLayout, *,
                        local_rows: bool):
    """(S, m, seg_cap) stacked slot->row tables for the sharded fused step.

    Segment slot j of shard s for group i draws
    ``u = uniform01(hash3(seed, i, s*seg_cap + j))`` -- the same stream
    family as :func:`counter_slot_table`, indexed by the buffer-global slot
    id -- and maps it into shard s's local sub-extent of group i.  With
    ``local_rows=True`` rows index the shard's own values slice (the mesh
    path); with ``local_rows=False`` the shard's row-block offset is added,
    yielding global rows into the unsharded (or padded) table: the
    solo-emulation view of the *identical* binding.
    """
    from ..kernels import prng

    S, m = layout.lsizes.shape
    seg_cap = layout.seg_cap
    seed = jax.random.bits(
        jax.random.fold_in(sample_key, SLOT_SALT), (), jnp.uint32)
    lstarts = jnp.asarray(layout.lstarts, jnp.int32)
    lsizes = jnp.asarray(layout.lsizes, jnp.int32)
    gids = jnp.arange(m, dtype=jnp.uint32)[None, :, None]
    slots = (jnp.arange(S, dtype=jnp.uint32)[:, None, None]
             * jnp.uint32(seg_cap)
             + jnp.arange(seg_cap, dtype=jnp.uint32)[None, None, :])
    u = prng.uniform01(prng.hash3(seed, gids, slots))   # (S, m, seg_cap)
    draw = jnp.minimum((u * lsizes[..., None]).astype(jnp.int32),
                       jnp.maximum(lsizes[..., None] - 1, 0))
    rows = lstarts[..., None] + draw
    if not local_rows:
        rows = rows + (jnp.arange(S, dtype=jnp.int32)
                       * jnp.int32(layout.rows_per_shard))[:, None, None]
    return rows


# ---------------------------------------------------------------------------
# SampleStore: incremental permuted-prefix sampling (DESIGN.md SS3.2)
# ---------------------------------------------------------------------------

class _PrefixPermutation:
    """Lazily-materialized uniform random permutation of ``[0, size)``.

    Incremental Fisher-Yates with a sparse swap map: materializing positions
    ``[t, upto)`` costs O(upto - t) time and O(upto) memory regardless of
    ``size`` -- a group's extent is never scanned.  Entries are materialized
    in ``page``-sized chunks so repeated tiny extensions amortize the host
    loop; materializing permutation *indices* ahead of need touches no data
    rows (rows are only touched when gathered by a binding).
    """

    __slots__ = ("size", "page", "_rng", "_perm", "_len", "_swaps")

    def __init__(self, size: int, rng: np.random.Generator, *, page: int = 512):
        self.size = int(size)
        self.page = int(page)
        self._rng = rng
        self._perm = np.empty((0,), np.int64)
        self._len = 0
        self._swaps: Dict[int, int] = {}

    def prefix(self, n: int) -> np.ndarray:
        """First ``n`` entries of the permutation (local offsets)."""
        n = min(int(n), self.size)
        if n > self._len:
            upto = min(-(-n // self.page) * self.page, self.size)
            if upto > len(self._perm):
                cap = max(2 * len(self._perm), upto)
                new = np.empty((min(cap, self.size),), np.int64)
                new[: self._len] = self._perm[: self._len]
                self._perm = new
            sw = self._swaps
            # Pre-draw uniforms so the Python loop does dict ops only:
            # r = j + floor(u * (size - j)) is uniform on [j, size).
            u = self._rng.random(upto - self._len)
            for j in range(self._len, upto):
                r = j + int(u[j - self._len] * (self.size - j))
                vj = sw.get(j, j)
                vr = sw.get(r, r)
                self._perm[j] = vr
                sw[r] = vj
            self._len = upto
        return self._perm[:n]


class SampleStoreBinding:
    """One value-column binding of a :class:`SampleStore`.

    The store owns the per-group permutations (the *which rows* state); a
    binding owns a device-resident gathered-row buffer over one values array
    (the *row contents* state).  The primary binding gathers from
    ``store.data.values``; predicate queries bind a derived indicator column
    to the same permutations, so every binding of a store sees the *same*
    nested row prefixes (AQPEngine reuses pilot + predicate rows this way).
    """

    def __init__(self, store: "SampleStore", values: Array):
        self.store = store
        self.values = jnp.asarray(values)
        if self.values.ndim == 1:
            self.values = self.values[:, None]
        self._buf: Optional[Array] = None       # (m, capacity, c)
        self._gathered = np.zeros((store.num_groups,), np.int64)
        self._epoch = store.epoch
        self.rows_touched = 0                   # cumulative gathered rows

    # -- internal -----------------------------------------------------------
    def _sync_epoch(self) -> None:
        if self._epoch != self.store.epoch:
            # Invalidation: permutations were refreshed/reshuffled under us.
            self._buf = None
            self._gathered[:] = 0
            self._epoch = self.store.epoch

    def _ensure_capacity(self, cap: int) -> None:
        c = self.values.shape[1]
        m = self.store.num_groups
        if self._buf is None:
            self._buf = jnp.zeros((m, cap, c), self.values.dtype)
        elif self._buf.shape[1] < cap:
            pad = cap - self._buf.shape[1]
            self._buf = jnp.pad(self._buf, ((0, 0), (0, pad), (0, 0)))

    # -- internal: window resolution ----------------------------------------
    def _window(self, n_vec, base) -> Tuple[np.ndarray, np.ndarray]:
        """Clamp a (base, n) permutation window against the group extents.

        ``base=None`` is the plain prefix ``[0, n)``.  A nonzero base reads
        slots ``[base, base + n)`` -- used for the stacked *init windows* of
        MISS: disjoint windows give the WLS fit independent probes, while
        their union is exactly the prefix the prediction phase then reuses.
        A window overrunning a group's extent is shifted back (overlapping
        earlier rows) so the sample never silently shrinks.
        """
        sizes = self.store.sizes
        n = np.minimum(np.asarray(n_vec, np.int64), sizes)
        if base is None:
            b = np.zeros_like(n)
        else:
            b = np.minimum(np.asarray(base, np.int64), np.maximum(sizes - n, 0))
        return b, n

    # -- public -------------------------------------------------------------
    def sample_cost(self, n_vec: np.ndarray, base=None) -> int:
        """Rows a ``sample(n_vec, base)`` call would actually gather."""
        self._sync_epoch()
        b, n = self._window(n_vec, base)
        return int(np.maximum(b + n - self._gathered, 0).sum())

    def sample(self, n_vec: np.ndarray, base=None) -> Tuple[Array, Array]:
        """Permuted-prefix sample of ``n_vec[i]`` rows per group.

        Returns ``(sample (m, n_cap, c), mask (m, n_cap))`` where ``n_cap``
        is the power-of-two bucket of the REQUESTED max size (not the
        store's resident capacity, which only grows) -- downstream jitted
        estimators stay sized to the query, and a long-lived store serving
        one large query doesn't widen every later small one.  Only rows not
        already resident are gathered; repeated calls with non-increasing
        sizes touch nothing.  With ``base``, row i of the result holds
        permutation slots ``[base[i], base[i] + n[i])`` left-aligned at
        column 0.
        """
        self._sync_epoch()
        store = self.store
        b, n = self._window(n_vec, base)
        need = b + n
        store.reserve(int(need.max(initial=1)))
        out_cap = bucket_cap(int(n.max(initial=1)))
        # Buffer sized to THIS binding's resident need, not the store-wide
        # high-water mark: a short-lived predicate binding must not inherit
        # the widest query's buffer.
        self._ensure_capacity(bucket_cap(int(need.max(initial=1))))
        grow = np.flatnonzero(need > self._gathered)
        if grow.size:
            g_pos: List[np.ndarray] = []
            s_pos: List[np.ndarray] = []
            idx: List[np.ndarray] = []
            for i in grow:
                lo, hi = int(self._gathered[i]), int(need[i])
                loc = store.perm(i).prefix(hi)[lo:hi]
                idx.append(store.offsets[i] + loc)
                s_pos.append(np.arange(lo, hi, dtype=np.int64))
                g_pos.append(np.full((hi - lo,), i, np.int64))
            flat_idx = np.concatenate(idx)
            rows = self.values[jnp.asarray(flat_idx)]          # (K, c) gather
            self._buf = self._buf.at[
                jnp.asarray(np.concatenate(g_pos)),
                jnp.asarray(np.concatenate(s_pos)),
            ].set(rows)
            self._gathered[grow] = need[grow]
            self.rows_touched += int(flat_idx.shape[0])
            store._note_rows(int(flat_idx.shape[0]))
        mask = (jnp.arange(out_cap)[None, :] < jnp.asarray(n)[:, None]).astype(
            jnp.float32)
        if base is None or not b.any():
            return self._buf[:, :out_cap], mask
        # Left-align the windows: column j of row i reads slot b[i] + j.
        slots = jnp.asarray(b)[:, None] + jnp.arange(out_cap)[None, :]
        slots = jnp.minimum(slots, self._buf.shape[1] - 1)
        window = jnp.take_along_axis(self._buf, slots[:, :, None], axis=1)
        return window, mask

    def sample_host(self, n_vec: np.ndarray,
                    base=None) -> Tuple[np.ndarray, np.ndarray]:
        """Host-path reference: same prefixes gathered with numpy.

        Used by parity tests -- must agree elementwise with the masked region
        of :meth:`sample`.
        """
        store = self.store
        b, n = self._window(n_vec, base)
        store.reserve(int((b + n).max(initial=1)))
        out_cap = bucket_cap(int(n.max(initial=1)))
        vals = np.asarray(self.values)
        m = store.num_groups
        out = np.zeros((m, out_cap, vals.shape[1]), vals.dtype)
        mask = np.zeros((m, out_cap), np.float32)
        for i in range(m):
            lo, k = int(b[i]), int(n[i])
            loc = store.perm(i).prefix(lo + k)[lo:lo + k]
            out[i, :k] = vals[store.offsets[i] + loc]
            mask[i, :k] = 1.0
        return out, mask

    def prefix_indices(self, n_vec: np.ndarray,
                       base=None) -> Tuple[np.ndarray, np.ndarray]:
        """Global row indices of the current windows (idx (m, cap), mask)."""
        store = self.store
        b, n = self._window(n_vec, base)
        store.reserve(int((b + n).max(initial=1)))
        out_cap = bucket_cap(int(n.max(initial=1)))
        idx = np.zeros((store.num_groups, out_cap), np.int64)
        mask = np.zeros((store.num_groups, out_cap), np.float32)
        for i in range(store.num_groups):
            lo, k = int(b[i]), int(n[i])
            idx[i, :k] = store.offsets[i] + store.perm(i).prefix(lo + k)[lo:lo + k]
            mask[i, :k] = 1.0
        return idx, mask


class SampleStore:
    """Device-resident incremental sample store over one :class:`GroupedData`.

    Semantics (DESIGN.md SS3.2):

      * ``sample(n)`` == first ``n`` entries of a per-group uniform random
        permutation -- samples are *nested*: ``sample(n)`` is always a prefix
        of ``sample(n + delta)`` within one epoch (without replacement, so
        ``sample(|group|)`` is the exact extent).
      * growing ``n -> n + delta`` gathers exactly ``delta`` new rows; the
        cumulative gather count is exposed as ``rows_touched`` and predicted
        by ``sample_cost`` (MISS's delta-based cost proxy).
      * ``refresh()`` invalidates after a data update (new permutations, new
        epoch); ``reshuffle()`` redraws permutations over the same data so
        long-lived servers don't correlate answers forever.
      * ``bind(values)`` attaches a derived value column (e.g. a predicate
        indicator) to the same permutations.

    The device buffer is padded to a power-of-two ``capacity`` bucket
    (``bucket_cap``) so downstream jitted estimators compile once per bucket.
    """

    def __init__(self, data: GroupedData, *, seed: int = 0, page: int = 512):
        self.data = data
        self.seed = int(seed)
        self.page = int(page)
        self.epoch = 0
        self.rows_touched = 0       # aggregate over all bindings
        self._capacity = 0
        self._perms: List[Optional[_PrefixPermutation]] = []
        self._reset_perms()
        self._primary = self.bind(data.values)

    # -- permutation state --------------------------------------------------
    def _reset_perms(self) -> None:
        root = np.random.default_rng((self.seed, self.epoch))
        self._seeds = root.integers(0, 2**63 - 1, size=self.num_groups)
        self._perms = [None] * self.num_groups

    def perm(self, i: int) -> _PrefixPermutation:
        p = self._perms[i]
        if p is None:
            p = _PrefixPermutation(
                int(self.sizes[i]),
                np.random.default_rng(int(self._seeds[i])),
                page=self.page)
            self._perms[i] = p
        return p

    def _note_rows(self, k: int) -> None:
        self.rows_touched += k

    # -- properties ---------------------------------------------------------
    @property
    def num_groups(self) -> int:
        return self.data.num_groups

    @property
    def sizes(self) -> np.ndarray:
        return self.data.sizes

    @property
    def offsets(self) -> np.ndarray:
        return self.data.offsets

    @property
    def capacity(self) -> int:
        """Current padded sample capacity (power-of-two jit bucket)."""
        return self._capacity

    def reserve(self, n: int) -> int:
        """Grow the capacity bucket to cover ``n``; returns the new capacity."""
        cap = bucket_cap(max(int(n), 1))
        if cap > self._capacity:
            self._capacity = cap
        return self._capacity

    # -- sampling (delegates to the primary binding) ------------------------
    def sample(self, n_vec: np.ndarray, base=None) -> Tuple[Array, Array]:
        return self._primary.sample(n_vec, base)

    def sample_host(self, n_vec: np.ndarray,
                    base=None) -> Tuple[np.ndarray, np.ndarray]:
        return self._primary.sample_host(n_vec, base)

    def sample_cost(self, n_vec: np.ndarray, base=None) -> int:
        return self._primary.sample_cost(n_vec, base)

    def prefix_indices(self, n_vec: np.ndarray, base=None):
        return self._primary.prefix_indices(n_vec, base)

    def bind(self, values: Array) -> SampleStoreBinding:
        """Attach a derived values column to this store's permutations.

        Bindings are not tracked by the store (no strong refs -- a predicate
        query's binding is garbage once the query returns); invalidation is
        lazy via the epoch counter each binding checks on use.
        """
        return SampleStoreBinding(self, values)

    # -- invalidation -------------------------------------------------------
    def refresh(self, data: Optional[GroupedData] = None) -> None:
        """Invalidate after a data update (or rebind to ``data``).

        All permutations are redrawn (sizes may have changed) and every
        binding's resident buffer is dropped; the primary binding follows the
        new ``data.values``.  ``rows_touched`` keeps accumulating -- it counts
        real work done, which survives invalidation.
        """
        if data is not None:
            self.data = data
            self._primary.values = jnp.asarray(
                data.values if data.values.ndim == 2 else data.values[:, None])
            self._primary._gathered = np.zeros((self.num_groups,), np.int64)
        self.epoch += 1
        self._reset_perms()

    def reshuffle(self, seed: Optional[int] = None) -> None:
        """Redraw permutations over the same data (decorrelation policy).

        A resident store shared by every query of a tenant would otherwise
        answer repeated queries from perfectly correlated prefixes; servers
        call this periodically (serve/aqp_service.py ``reshuffle_every``).
        """
        if seed is not None:
            self.seed = int(seed)
        self.epoch += 1
        self._reset_perms()
