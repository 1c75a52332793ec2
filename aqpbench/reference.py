"""Plain reference for the answers the serving window produced.

An ``AQPSession`` answer states three things: the estimate ``theta``, the
sample sizes ``n`` it came from, and the bootstrap error bar ``error`` (the
``1 - delta`` quantile, over ``B`` Poisson(1) replicates, of the L2
distance of a replicate from ``theta``).  The program's design makes each of
them a pure function of the request's bootstrap key, the session's sample
key, and the tick at which the lane last estimated (DESIGN.md SS7 phases C
to I):

* slot ``j`` of group ``g`` reads row ``start_g + floor(u * size_g)``, with
  ``u`` the top 24 bits of a murmur3 counter hash of ``(slot seed, g, j)``
  (a grouped block gives each group its own slot seed);
* a lane's last estimate covers the slot window ``[0, n_g)``, or, while the
  lane is still in its two-point init design, the stacked window that
  design prescribes;
* replicate ``b`` weighs slot ``j`` by ``Poisson1(hash(seed_g(k), j, b))``.

This module recomputes ``theta`` and ``error`` from the table in float64
NumPy, with nothing of the program imported: it rebuilds the keys with
``jax.random`` from the seeds the benchmark chose, finds the sample epoch
and the window by ``theta`` (a wrong one is as a rule off by about 1e-3;
where two come within ``THETA_SLACK``, the error bar decides), and the tick
by ``error`` (a wrong tick is off by about 1e-2).  The tick search runs in
float32 on the device; ticks it cannot tell apart, and the numbers compared,
are float64.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

C_ROW, C_COL, C_SEED = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D
SALT_SLOT, SALT_BOOT, SALT_GROUP = 0x5A17, 0xB007, 0x7F4A7C15
POISSON1_CDF = (
    0.36787944117144233, 0.7357588823428847, 0.9196986029286058,
    0.9810118431238462, 0.9963401531726563, 0.9994058151824183,
    0.9999167588507119, 0.9999897508033253, 0.9999988747974149,
    0.9999998885745217,
)
# u >= c  <=>  (bits >> 8) >= c * 2**24, with c rounded to float32 first.
THRESH = np.ceil(np.asarray(POISSON1_CDF, np.float32).astype(np.float64)
                 * 2.0 ** 24).astype(np.int64)
MEAN_FUNCS = ("avg", "sum", "count", "proportion")
SCALED_FUNCS = ("sum", "count")
ROW_CHUNK = 8192
TICK_SLACK = 5e-3      # float32 search: candidates this close go to float64
THETA_SLACK = 1e-4     # epochs and windows this close in theta: error bar


# -- counter hash, NumPy ------------------------------------------------------

def _u32(a) -> np.ndarray:
    return np.asarray(a).astype(np.uint32)


def mix32(h: np.ndarray) -> np.ndarray:
    u32 = np.uint32
    with np.errstate(over="ignore"):
        h = h ^ (h >> u32(16))
        h = h * u32(0x7FEB352D)
        h = h ^ (h >> u32(15))
        h = h * u32(0x846CA68B)
        h = h ^ (h >> u32(16))
    return h


def hash3(seed, row, col) -> np.ndarray:
    u32 = np.uint32
    with np.errstate(over="ignore"):
        return mix32(_u32(row) * u32(C_ROW) ^ _u32(col) * u32(C_COL)
                     ^ _u32(seed) * u32(C_SEED))


def poisson1(bits: np.ndarray) -> np.ndarray:
    """Inverse-CDF Poisson(1) counts from hash bits (truncated at 10)."""
    return np.searchsorted(THRESH, (bits >> np.uint32(8)).astype(np.int64),
                           side="right")


def key_bits(key, salt: int) -> int:
    """``uint32`` bits of ``fold_in(key, salt)`` (a seed base)."""
    k = jax.random.fold_in(jnp.asarray(key, jnp.uint32), salt)
    return int(jax.device_get(jax.random.bits(k, (), jnp.uint32)))


def fold_in(key, data: int) -> np.ndarray:
    return np.asarray(jax.device_get(
        jax.random.fold_in(jnp.asarray(key, jnp.uint32), data)), np.uint32)


def slot_rows(slot_seed: int, row_id: int, start: int, size: int,
              n: int, first: int = 0) -> np.ndarray:
    """Table rows of slots ``first..first+n-1`` of one group's extent
    ``[start, start + size)`` (float32 arithmetic, as the slot binding
    states it)."""
    bits = hash3(slot_seed, row_id,
                 np.arange(first, first + n, dtype=np.uint32))
    u = (bits >> np.uint32(8)).astype(np.int32).astype(np.float32) \
        * np.float32(2.0 ** -24)
    idx = (u * np.float32(size)).astype(np.int32)
    return start + np.minimum(idx, size - 1).astype(np.int64)


# -- the estimate, float64 ----------------------------------------------------

def _finish(M: np.ndarray, func: str) -> np.ndarray:
    """Moment sums ``[..., 3]`` -> the estimator's value."""
    m0 = np.maximum(M[..., 0], 1e-30)
    mu = M[..., 1] / m0
    if func in MEAN_FUNCS:
        return mu
    if func == "var":
        return M[..., 2] / m0 - mu * mu
    raise ValueError(f"no moment finish for {func!r}")


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 (round to nearest even) -> float64."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def replicate_moments(x: np.ndarray, slots: np.ndarray, seed: int, B: int,
                      *, control: bool = False) -> np.ndarray:
    """``(B, 3)`` sums ``[sum w, sum w x, sum w x^2]`` over the window.

    ``control`` rounds the features to bfloat16 before the contraction, as
    an f32 matrix product at the TPU's default precision does.
    """
    x64 = np.asarray(x, np.float32).astype(np.float64)
    if control:
        feats = np.stack([np.ones_like(x64), bf16_round(x64),
                          bf16_round(np.float32(x64) * np.float32(x64))], 1)
    else:
        feats = np.stack([np.ones_like(x64), x64, x64 * x64], 1)
    cols = np.arange(B, dtype=np.uint32)[None, :]
    M = np.zeros((B, 3))
    for c in range(0, len(x64), ROW_CHUNK):
        s = slots[c:c + ROW_CHUNK]
        W = poisson1(hash3(seed, s[:, None], cols)).astype(np.float64)
        M += W.T @ feats[c:c + ROW_CHUNK]
    return M


def estimate(parts: Sequence[Sequence[Tuple[np.ndarray, np.ndarray, int]]],
             func: str, B: int, *,
             control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """``(theta (m,), replicates (m, B))`` of one lane, unscaled.

    ``parts[g]`` lists group g's ``(x, slots, seed)``: its values, their
    slot positions and their bootstrap seed, one part a shard (one in all
    on a single shard).  The parts' moment sums are added in float64, then
    the dead-replicate guard and the finish run on the sum.  ``control``
    computes the whole estimate on bfloat16 operands (float32 products and
    float64 sums), the precision below the configuration's.
    """
    thetas, reps = [], []
    for group in parts:
        plain, M = np.zeros(3), np.zeros((B, 3))
        for x, s, seed in group:
            x64 = np.asarray(x, np.float32).astype(np.float64)
            if control:
                x2 = bf16_round(np.float32(x64) * np.float32(x64))
                x64 = bf16_round(x64)
            else:
                x2 = x64 * x64
            plain += [len(x64), x64.sum(), x2.sum()]
            M += replicate_moments(x, s, seed, B, control=control)
        M = np.where(M[:, :1] <= 0, plain[None, :], M)
        thetas.append(_finish(plain, func))
        reps.append(_finish(M, func))
    return np.asarray(thetas), np.stack(reps, 0)


def error_bar(reps: np.ndarray, theta: np.ndarray, scale: np.ndarray,
              delta: float) -> float:
    """The ``1 - delta`` quantile of the replicates' L2 distance from
    ``theta`` (both unscaled), in the answer's scale."""
    dev = (reps - theta[:, None]) * scale[:, None]
    return float(np.quantile(np.sqrt(np.sum(dev * dev, axis=0)), 1.0 - delta))


# -- the tick search, float32 on the device -----------------------------------

def _hash3_j(seed, row, col):
    u32 = jnp.uint32

    def mix(h):
        h = h ^ (h >> u32(16))
        h = h * np.uint32(0x7FEB352D)
        h = h ^ (h >> u32(15))
        h = h * np.uint32(0x846CA68B)
        return h ^ (h >> u32(16))
    return mix(row.astype(u32) * np.uint32(C_ROW)
               ^ col.astype(u32) * np.uint32(C_COL)
               ^ seed.astype(u32) * np.uint32(C_SEED))


@functools.partial(jax.jit, static_argnames=("B", "var", "shards"))
def _search_errors(x, lo, hi, seeds, scale, q, *, B: int, var: bool,
                   shards: int):
    """Error bars of one lane for each candidate seed row ``(C, m S)``.

    Each group has ``shards`` S rows of ``x`` (its part on each shard, row
    ``g * S + s``, with the shard's own seed and slot positions), whose
    moment sums are added before the finish.
    """
    m, W = x.shape
    j = jnp.arange(W, dtype=jnp.int32)
    mask = ((j[None] >= lo[:, None]) & (j[None] < hi[:, None])).astype(
        jnp.float32)
    feats = jnp.stack([mask, mask * x, mask * x * x], -1)         # (m, W, 3)
    plain = feats.sum(1)                                           # (m, 3)
    if shards > 1:
        plain = plain.reshape(-1, shards, 3).sum(1)              # (m, 3)
    cols = jnp.arange(B, dtype=jnp.uint32)

    def fin(M):
        m0 = jnp.maximum(M[..., 0], 1e-30)
        mu = M[..., 1] / m0
        return M[..., 2] / m0 - mu * mu if var else mu

    theta = fin(plain)

    def one(seed_row):
        h = _hash3_j(seed_row[:, None, None], j[None, :, None],
                     cols[None, None, :])
        v = (h >> jnp.uint32(8)).astype(jnp.int32)
        w = sum((v >= t).astype(jnp.float32) for t in THRESH.tolist())
        M = jnp.einsum("mwb,mwp->mbp", w, feats,
                       precision=jax.lax.Precision.HIGHEST)
        if shards > 1:
            M = M.reshape(-1, shards, B, 3).sum(1)
        M = jnp.where(M[..., :1] <= 0, plain[:, None, :], M)
        dev = (fin(M) - theta[:, None]) * scale[:, None]
        return jnp.quantile(jnp.sqrt(jnp.sum(dev * dev, 0)), q)

    return jax.lax.map(one, seeds)


def _bucket(n: int) -> int:
    """Padded width of the search's own program (few compiled shapes)."""
    w = 1024
    while w < n:
        w *= 2
    return w


# -- one answer ---------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What the benchmark recorded of one finished fused answer."""
    func: str
    delta: float
    key: np.ndarray            # (2,) uint32 bootstrap key it submitted
    theta: np.ndarray          # (m,) scaled estimates
    error: np.ndarray          # (1,) solo, (G,) grouped
    n: np.ndarray              # (m,)
    group_by: bool


@dataclasses.dataclass
class Check:
    """Gaps of one answer, one entry per lane: the solo answer's, or each
    group's of a grouped block."""
    theta_gaps: List[float]        # relative gap of theta to float64
    errbar_gaps: List[float]       # relative gap of the error bar to float64
    ctrl_theta_gaps: List[float]   # the same, bfloat16 control in its place
    ctrl_errbar_gaps: List[float]
    epoch: int
    ticks: List[int]


class Reference:
    """Recomputes served answers over one table (host float32 copy)."""

    def __init__(self, values: np.ndarray, offsets: np.ndarray, *,
                 session_seed: int, B: int, n_min: int, n_max: int,
                 n_cap: int, max_iters: int, l: int, ext_cap: int):
        """The trajectory parameters are those the serving session ran
        with (read off it, not assumed): ``l`` init-design probes, of which
        the first ``l_min`` take ``n_min`` rows (the paper's Eq. 15/16), and
        at most ``ext_cap`` new slots per tick."""
        self.values = values
        self.offsets = np.asarray(offsets, np.int64)
        self.sizes = np.diff(self.offsets)
        self.m = len(self.sizes)
        self.B, self.n_min, self.n_max = B, n_min, n_max
        self.n_cap, self.max_iters, self.ext_cap = n_cap, max_iters, ext_cap
        self.l = int(l)
        self.l_min = min(max(int(round(self.l * n_max / (n_min + n_max))), 1),
                         self.l - 1)
        self._root = np.asarray(jax.device_get(
            jax.random.PRNGKey(session_seed ^ SALT_SLOT)), np.uint32)
        self._rows: Dict[Tuple[int, bool, int], np.ndarray] = {}

    # -- sample binding
    def rows(self, epoch: int, grouped: bool, g: int) -> np.ndarray:
        """Table rows of every slot of group ``g`` in sample epoch ``epoch``."""
        k = (epoch, grouped, g)
        if k not in self._rows:
            sample_key = fold_in(self._root, epoch)
            if grouped:
                seed, row_id = key_bits(fold_in(sample_key, g), SALT_SLOT), 0
            else:
                seed, row_id = key_bits(sample_key, SALT_SLOT), g
            # n_cap slots whatever the group's size: the slots of a group
            # smaller than n_cap bind its rows again (the init design's
            # stacked windows reach past them).
            self._rows[k] = slot_rows(seed, row_id, int(self.offsets[g]),
                                      int(self.sizes[g]), self.n_cap)
        return self._rows[k]

    # -- windows
    def window(self, k: int, n: np.ndarray, group_ids: Sequence[int]
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Slot window of the estimate at tick ``k`` for a lane that ends at
        sizes ``n`` (None where the init design cannot end there)."""
        if k >= self.l:
            return np.zeros_like(n), n.copy()
        m = len(n)
        cap = np.minimum(self.sizes[list(group_ids)], self.n_cap)
        filled = np.zeros(m, np.int64)
        for t in range(k + 1):
            phase = (t + np.arange(m)) % self.l
            nt = np.where(phase < self.l_min, self.n_min, self.n_max)
            nt = np.minimum(np.clip(nt, 1, cap), filled + self.ext_cap)
            lo = np.minimum(filled, self.n_cap - nt)
            hi = lo + nt
            filled = np.maximum(filled, hi)
        return (lo, hi) if np.array_equal(hi - lo, n) else None

    def _theta(self, x64: np.ndarray, func: str, scale: float) -> float:
        mu = x64.mean()
        val = mu if func in MEAN_FUNCS else np.mean(x64 * x64) - mu * mu
        return float(val) * scale

    def scale_of(self, func: str, g: int) -> float:
        return float(self.sizes[g]) if func in SCALED_FUNCS else 1.0

    # -- the window's parts and their bootstrap seeds
    def parts(self, epoch: int, grouped: bool, group_ids: Sequence[int],
              lo: np.ndarray, hi: np.ndarray
              ) -> List[List[Tuple[np.ndarray, int, int]]]:
        """Per group, its window's parts ``(x, a, b)``, one per shard: the
        values of slots ``[a, b)`` (one part on a single shard)."""
        return [[(self.values[self.rows(epoch, grouped, g)[a:b]], int(a),
                  int(b))] for g, a, b in zip(group_ids, lo, hi)]

    def seeds(self, bases: Sequence[int], k: int, grouped: bool,
              group_ids: Sequence[int]) -> List[List[int]]:
        """``(m, S)`` bootstrap seeds of each group's part on each shard at
        tick ``k``."""
        return [[int(hash3(hash3(b, k, SALT_GROUP), 0 if grouped else i,
                           SALT_GROUP))] for i, b in enumerate(bases)]

    # -- checks
    def check_lane(self, s: Served, epochs: int, group_ids: Sequence[int],
                   key_per_group: Sequence[np.ndarray], theta: np.ndarray,
                   error: float, n: np.ndarray, grouped: bool,
                   control: bool = False
                   ) -> Tuple[float, float, float, float, int, int]:
        """One lane (a solo answer, or one group of a grouped block)."""
        scale = np.array([self.scale_of(s.func, g) for g in group_ids])
        windows: Dict[Tuple, List[int]] = {}
        for k in range(self.max_iters):
            w = self.window(k, n, group_ids)
            if w is not None:
                windows.setdefault((tuple(w[0]), tuple(w[1])), []).append(k)
        cands = []
        for e in range(epochs + 1):
            rows = [self.rows(e, grouped, g) for g in group_ids]
            for (lo, hi), ks in windows.items():
                if any(h > len(r) for h, r in zip(hi, rows)):
                    continue
                gap = max(abs(self._theta(
                    self.values[r[a:b]].astype(np.float64), s.func, sc) - t)
                    / max(abs(t), 1e-30)
                    for r, a, b, sc, t in zip(rows, lo, hi, scale, theta))
                cands.append((gap, e, np.asarray(lo), np.asarray(hi), ks))
        if not cands:
            return np.inf, np.inf, np.inf, np.inf, -1, -1
        # A wrong epoch or window is as a rule off by about 1e-3 in theta,
        # but two epochs' samples can agree far closer than that: theta
        # cannot tell apart candidates within THETA_SLACK of the best, and
        # the error bar, drawn from the lane's own key, decides.
        best = min(c[0] for c in cands)
        return min((self._fit_ticks(s, e, lo, hi, ks, group_ids,
                                    key_per_group, theta, error, grouped,
                                    scale, control)
                    for gap, e, lo, hi, ks in cands
                    if gap <= best + THETA_SLACK), key=lambda out: out[1])

    def _fit_ticks(self, s: Served, epoch: int, lo: np.ndarray,
                   hi: np.ndarray, ks: List[int], group_ids: Sequence[int],
                   key_per_group: Sequence[np.ndarray], theta: np.ndarray,
                   error: float, grouped: bool, scale: np.ndarray,
                   control: bool
                   ) -> Tuple[float, float, float, float, int, int]:
        """The gaps of one (epoch, window) candidate at the tick whose
        error bar lies closest to the program's."""
        m = len(group_ids)
        parts = self.parts(epoch, grouped, group_ids, lo, hi)
        S = len(parts[0])
        bases = [key_bits(kg, SALT_BOOT) for kg in key_per_group]

        def parts_at(k):
            return [[(x, np.arange(a, b, dtype=np.uint32), sd)
                     for (x, a, b), sd in zip(pg, sg)]
                    for pg, sg in zip(parts, self.seeds(bases, k, grouped,
                                                        group_ids))]

        if len(ks) > 1:
            W = _bucket(max(b for pg in parts for _, _, b in pg))
            xp = np.zeros((m * S, W), np.float32)
            for i, pg in enumerate(parts):
                for sh, (x, a, b) in enumerate(pg):
                    xp[i * S + sh, a:b] = x
            los = np.asarray([a for pg in parts for _, a, _ in pg], np.int32)
            his = np.asarray([b for pg in parts for _, _, b in pg], np.int32)
            cand = np.asarray([np.ravel(self.seeds(bases, k, grouped,
                                                   group_ids)) for k in ks],
                              np.uint32)
            errs = np.asarray(jax.device_get(_search_errors(
                jnp.asarray(xp), jnp.asarray(los), jnp.asarray(his),
                jnp.asarray(cand), jnp.asarray(scale, jnp.float32),
                jnp.float32(1.0 - s.delta), B=self.B, var=s.func == "var",
                shards=S)))
            # The search centres on its own float32 theta, whose rounding
            # |theta| / error amplifies: it tells ticks apart only to about
            # 1e-3 of the error bar.  Ticks that close are told apart in
            # float64 below.
            rel = np.abs(errs / max(error, 1e-30) - 1.0)
            ks = [k for k, r in zip(ks, rel) if r <= rel.min() + TICK_SLACK]

        def gaps(th, e, reps):
            """theta's gap to float64, and the error bar's gap to the float64
            replicates' error bar around that same theta: the second reads
            the replicate moment sums alone."""
            return (float(np.max(np.abs(th - ref_th * scale)
                                 / np.maximum(np.abs(ref_th * scale), 1e-30))),
                    abs(e - (q := error_bar(reps, th / scale, scale,
                                            s.delta))) / max(q, 1e-30))

        fits = {k: estimate(parts_at(k), s.func, self.B) for k in ks}
        ref_th = fits[ks[0]][0]            # theta is the same at every tick
        k = min(ks, key=lambda k: gaps(theta, error, fits[k][1])[1])
        ref_reps = fits[k][1]
        out = list(gaps(theta, error, ref_reps)) + [np.nan, np.nan]
        if control:
            # The control: the reference on bfloat16 operands, put in the
            # program's place and judged as the program is.
            c_th, c_reps = estimate(parts_at(k), s.func, self.B,
                                    control=True)
            out[2:] = gaps(c_th * scale,
                           error_bar(c_reps, c_th, scale, s.delta), ref_reps)
        return out[0], out[1], out[2], out[3], epoch, k

    def check(self, s: Served, epochs: int, control: bool = False) -> Check:
        """Recompute one served answer; ``control`` also reads the bfloat16
        control against the float64 reference."""
        if not s.group_by:
            parts = [self.check_lane(
                s, epochs, range(self.m), [s.key] * self.m, s.theta,
                float(s.error[0]), s.n, grouped=False, control=control)]
        else:
            parts = [self.check_lane(
                s, epochs, [g], [fold_in(s.key, g)], s.theta[g:g + 1],
                float(s.error[g]), s.n[g:g + 1], grouped=True,
                control=control) for g in range(self.m)]
        return Check(*([p[i] for p in parts] for i in range(4)),
                     parts[0][4], [p[5] for p in parts])
