"""L2Miss (paper Algorithm 3): the concrete SSO algorithm for the L2 metric.

Host loop = Algorithm 1 (core/framework.py); all numeric subroutines are
jitted fixed-shape device programs, cached per (m, n_cap, B) bucket so a full
MISS run compiles only O(log final_size) distinct programs:

  SAMPLE    stratified_sample     (core/sampling.py)
  ESTIMATE  Poisson bootstrap     (core/bootstrap.py, kernels/poisson_bootstrap)
  PREDICT   WLS fit + Algorithm-2 diagnostic + Eq.-13 closed form
            (core/error_model.py)

Implementation hardening vs. the paper (recorded in DESIGN.md SS9):
  * growth guard: when the constraint is unmet, n^(k+1) >= n^(k) + 1
    elementwise (Lemma 5 gives this under ideal fits; we enforce it so
    termination never hinges on fit quality);
  * exact fallback: if a group's predicted size reaches its population we
    clamp, and if every group is clamped we return the exact answer;
  * error floor: log e is clamped at LOG_FLOOR for degenerate zero errors.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bootstrap, error_model, sampling
from .estimators import Estimator, get as get_estimator
from .framework import MissFailure, MissTrace, run_miss
from ..kernels import resolve_use_kernel

LOG_FLOOR = -60.0


@dataclasses.dataclass
class MissConfig:
    """Parameters of Algorithm 3 (defaults follow paper SS6)."""

    epsilon: float                      # error bound (absolute, post-Gamma)
    delta: float = 0.05                 # error probability
    B: int = 500                        # bootstrap resamples
    n_min: int = 100                    # initialization interval I_n
    n_max: int = 200
    l: Optional[int] = None             # init length; default 5*(m+1) (SS6.3)
    tau: float = 1e-3                   # Algorithm-2 failure threshold
    max_iters: int = 64
    budget_rows: Optional[int] = None   # resource cap (failure type 1, SS4.3.4)
    backend: str = "poisson"            # bootstrap backend
    metric: str = "l2"
    growth_guard: bool = True
    # Trust region: cap the per-iteration growth of any group's size at
    # growth_cap x.  A noisy init fit can overshoot Eq. 13 by orders of
    # magnitude; stepping there directly both wastes sample budget AND
    # accepts at the overshoot (e <= eps holds there).  Intermediate steps
    # add high-leverage profile points, so the refit converges to the true
    # optimum -- Lemma 5 monotonicity and termination are unaffected.
    growth_cap: float = 8.0
    seed: int = 0
    # Bootstrap backend selection: True / False / "auto" ("auto" routes the
    # moment estimators through the Pallas kernel on TPU and stays on the
    # jnp path elsewhere -- kernels.resolve_use_kernel).
    use_kernel: "bool | str" = "auto"
    # Non-uniform linear sampling cost (paper SS8): minimize sum_i c_i n_i.
    cost_weights: Optional[Tuple[float, ...]] = None


@lru_cache(maxsize=64)
def _estimate_fn(est_name: str, m: int, n_cap: int, c: int, B: int,
                 backend: str, metric: str, use_kernel: bool):
    """Jit-compiled ESTIMATE for one shape bucket.

    SAMPLE moved out of the jitted program into the incremental SampleStore
    (permuted-prefix reuse); the bucket key ``n_cap`` is the store's current
    capacity, so a full MISS run still compiles only O(log final_size)
    distinct programs.
    """
    if use_kernel and est_name in ("avg", "proportion", "sum", "count", "var",
                                   "std"):
        from ..kernels.poisson_bootstrap import ops as pb_ops

        def fn(key, sample, mask, scale, delta):
            return pb_ops.estimate_error_moments(
                est_name, sample, mask, scale, key, delta, B=B, metric=metric)
    else:
        est = get_estimator(est_name)

        def fn(key, sample, mask, scale, delta):
            return bootstrap.estimate_error(
                est, sample, mask, scale, key, delta, B=B,
                backend=backend, metric=metric)

    return jax.jit(fn)


class _L2MissSubroutines:
    """Algorithm 3's concrete INITIALIZE/SAMPLE/ESTIMATE/PREDICT."""

    def __init__(self, data: sampling.GroupedData, est: Estimator,
                 cfg: MissConfig,
                 store: "sampling.SampleStore | sampling.SampleStoreBinding | None" = None):
        self.data = data
        self.est = est
        self.cfg = cfg
        self.m = data.num_groups
        self.sizes = data.sizes.astype(np.int64)
        self.key = sampling.root_key(cfg.seed)
        # Incremental permuted-prefix sampler: nested across iterations, so
        # growing n touches only the extension (DESIGN.md SS3.2).  A caller
        # may pass a resident store (AQPEngine/AQPSession) to reuse prefixes
        # across queries too.
        self.store = store if store is not None else sampling.SampleStore(
            data, seed=cfg.seed)
        # Per-run accounting baseline: a resident store's counter is
        # cumulative across queries; this run's rows are the delta from here.
        self._rows_at_start = int(self.store.rows_touched)
        self.scale = (
            np.asarray(data.scale, np.float32)
            if est.needs_population_scale
            else np.ones((self.m,), np.float32)
        )
        self.last_fit: Optional[error_model.ErrorModelFit] = None
        self._scale_dev = jnp.asarray(self.scale)
        self._prev_n: Optional[np.ndarray] = None
        self._all_clamped = False
        self._init_rows: Optional[np.ndarray] = None
        self._init_bases: Optional[np.ndarray] = None
        self._l = 0
        self._next_it = 0

    # -- INITIALIZE (SS4.4) -------------------------------------------------
    def initialize(self) -> np.ndarray:
        cfg = self.cfg
        # Default l: paper suggests >= m+1 for the regression but "not too
        # large"; 5(m+1) (their SS6.3 choice) uncapped starves the prediction
        # phase for m ~ 9, so cap at 16 while keeping l >= m+2.
        l = cfg.l if cfg.l is not None else max(
            self.m + 2, min(5 * (self.m + 1), 16))
        self.key, sub = jax.random.split(self.key)
        rows = sampling.two_point_init_sizes(sub, self.m, l, cfg.n_min, cfg.n_max)
        rows = np.minimum(rows, self.sizes[None, :])
        # Init probes read STACKED permutation windows: iteration k samples
        # slots [base_k, base_k + n_k), disjoint across k, so the WLS fit
        # sees independent draws (two probes at the same level must not be
        # the same rows).  Their union [0, sum n_k) is exactly the prefix
        # the prediction phase then reuses -- init costs the same rows as
        # fresh sampling, reuse kicks in from the first prediction.
        self._init_rows = rows
        self._init_bases = np.concatenate([
            np.zeros((1, self.m), np.int64),
            np.cumsum(rows[:-1], axis=0, dtype=np.int64),
        ])
        self._l = l
        return rows

    # -- SAMPLE (incremental, host-driven) + ESTIMATE (jitted per bucket) ----
    def _base_for(self, it: int):
        if getattr(self, "_init_bases", None) is not None and it < self._l:
            return self._init_bases[it]
        return None

    def sample_cost(self, n_vec: np.ndarray) -> int:
        """Rows the next SAMPLE call will actually gather (delta vs resident).

        The framework calls this right before ``sample`` with the same
        ``n_vec``; ``_next_it`` tracks which iteration that will be (init
        iterations read stacked windows, prediction reads the prefix).
        """
        return self.store.sample_cost(
            np.asarray(n_vec, np.int64), self._base_for(self._next_it))

    def sample(self, n_vec: np.ndarray, it: int):
        n_vec = np.minimum(np.asarray(n_vec, np.int64), self.sizes)
        sample, mask = self.store.sample(n_vec, self._base_for(it))
        self._next_it = it + 1
        return n_vec, sample, mask

    def estimate(self, handle, it: int) -> Tuple[float, np.ndarray]:
        cfg = self.cfg
        _, sample, mask = handle
        n_cap = sample.shape[1]   # = store capacity bucket
        fn = _estimate_fn(
            self.est.name, self.m, n_cap, self.data.num_columns, cfg.B,
            cfg.backend, cfg.metric, resolve_use_kernel(cfg.use_kernel))
        self.key, sub = jax.random.split(self.key)
        e, theta = fn(sub, sample, mask, self._scale_dev, cfg.delta)
        return float(e), np.asarray(theta)

    # -- PREDICT (SS4.3): WLS fit -> diagnose -> Eq. 13 ----------------------
    def predict(self, profile_n: np.ndarray, profile_e: np.ndarray, it: int):
        cfg = self.cfg
        loge = np.log(np.maximum(profile_e, np.exp(LOG_FLOOR)))
        valid = np.ones((len(loge),), np.float32)
        cw = (jnp.asarray(cfg.cost_weights, jnp.float32)
              if cfg.cost_weights is not None else None)
        n_hat, fit = error_model.fit_and_predict(
            jnp.asarray(profile_n, jnp.float32), jnp.asarray(loge, jnp.float32),
            jnp.asarray(valid), jnp.log(jnp.float32(cfg.epsilon)), cfg.tau,
            cost_weights=cw)
        self.last_fit = fit
        if int(fit.status) == error_model.DIAG_FAILURE:
            raise MissFailure("sum(beta) <= tau: error will not shrink with n")
        alloc = np.maximum(np.asarray(n_hat, np.float64), 1.0)
        prev = self._prev_n if self._prev_n is not None else profile_n.max(axis=0)
        # Local-model correction: if Eq.-13 total lands at/below the
        # proven-direction step from the last iterate (intercept misfit near
        # convergence), upscale the WHOLE allocation uniformly -- this keeps
        # the (possibly cost-weighted) allocation shape and can only reduce
        # H (feasible-safe), instead of crawling by +1.
        slopes = np.asarray(fit.beta)[1:]
        s = max(float(slopes.sum()), 1e-3)
        ratio = float(profile_e[-1]) / cfg.epsilon
        cost = (np.asarray(cfg.cost_weights, np.float64)
                if cfg.cost_weights is not None else np.ones(self.m))
        if ratio > 1.0:
            floor_alloc = profile_n[-1] * ratio ** (1.0 / s)
            c_hat = float((alloc * cost).sum())
            c_floor = float((floor_alloc * cost).sum())
            if c_hat < c_floor:
                alloc = alloc * (c_floor / c_hat)
        # Trust region on the TOTAL (cost-weighted) size, scaling the whole
        # allocation uniformly so the predicted shape survives clipping.
        c_alloc = float((alloc * cost).sum())
        c_cap = float((prev * cfg.growth_cap * cost).sum()) + 1.0
        if c_alloc > c_cap:
            alloc = alloc * (c_cap / c_alloc)
        n_next = np.ceil(alloc).astype(np.int64)
        if cfg.growth_guard:
            n_next = np.maximum(n_next, prev + 1)
        clamped = n_next >= self.sizes
        n_next = np.minimum(n_next, self.sizes)
        self._all_clamped = bool(clamped.all())
        self._prev_n = n_next
        info = {
            "beta": np.asarray(fit.beta),
            "r2": float(fit.r2),
            "diag_status": int(fit.status),
            "all_clamped": self._all_clamped,
        }
        return n_next, info


def exact_answer(data: sampling.GroupedData, est: Estimator) -> np.ndarray:
    """Ground-truth theta on the full dataset (used by tests/benchmarks)."""
    from .estimators import evaluate

    outs = []
    vals = np.asarray(data.values)
    for i in range(data.num_groups):
        seg = jnp.asarray(vals[data.offsets[i]:data.offsets[i + 1]])
        th = np.asarray(evaluate(est, seg))
        if est.needs_population_scale:
            th = th * data.scale[i]
        outs.append(th)
    return np.stack(outs)


def run_l2miss(
    data: sampling.GroupedData,
    estimator: "Estimator | str",
    cfg: MissConfig,
    store: "sampling.SampleStore | sampling.SampleStoreBinding | None" = None,
) -> MissTrace:
    """Run Algorithm 3 end to end on a grouped dataset.

    ``store``: optional resident :class:`~repro.core.sampling.SampleStore`
    (or a binding of one) whose nested prefixes this run extends and reuses;
    by default a run-local store is created, which still makes
    ``MissTrace.total_sampled`` delta-based across the run's iterations.
    """
    est = get_estimator(estimator) if isinstance(estimator, str) else estimator
    subs = _L2MissSubroutines(data, est, cfg, store=store)
    trace = run_miss(
        subs, cfg.epsilon, max_iters=cfg.max_iters, budget_rows=cfg.budget_rows
    )
    if subs.last_fit is not None:
        trace.info.setdefault("beta", np.asarray(subs.last_fit.beta))
        trace.info.setdefault("r2", float(subs.last_fit.r2))
    trace.info.setdefault(
        "rows_touched", int(subs.store.rows_touched) - subs._rows_at_start)
    return trace
