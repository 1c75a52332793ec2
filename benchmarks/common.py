"""Shared benchmark utilities: timing + simulated-confidence harness
(paper SS6.1) + CSV emission in the required `name,us_per_call,derived`
format."""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimators
from repro.core.l2miss import exact_answer
from repro.core.sampling import GroupedData, bucket_cap, stratified_sample


def timed(fn: Callable, *args, repeats: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt


def simulated_confidence(
    data: GroupedData, est_name: str, n_vec: np.ndarray, epsilon: float,
    *, metric: str = "l2", trials: int = 200, seed: int = 123,
    theta_truth: Optional[np.ndarray] = None,
) -> float:
    """Fraction of fresh samples of size n_vec meeting the bound (SS6.1)."""
    est = estimators.get(est_name)
    if theta_truth is None:
        theta_truth = exact_answer(data, est)
    truth = jnp.asarray(theta_truth.ravel(), jnp.float32)
    scale = jnp.asarray(
        data.scale if est.needs_population_scale else np.ones(data.num_groups),
        jnp.float32)
    n_cap = bucket_cap(int(max(n_vec)))
    n_dev = jnp.asarray(np.minimum(n_vec, data.sizes))
    offs = jnp.asarray(data.offsets)

    @jax.jit
    def one(key):
        sample, mask = stratified_sample(key, data.values, offs, n_dev, n_cap)
        th = jax.vmap(lambda xg, mg: est.apply(est.prepare(xg), mg))(
            sample, mask)
        err = (th[:, 0] * scale) - truth
        if metric == "l2":
            return jnp.sqrt(jnp.sum(err**2))
        if metric == "linf":
            return jnp.max(jnp.abs(err))
        raise ValueError(metric)

    keys = jax.random.split(jax.random.PRNGKey(seed), trials)
    errs = np.asarray(jax.vmap(one)(keys))
    return float((errs <= epsilon).mean())


class CsvEmitter:
    """Collects `name,us_per_call,derived` rows (skeleton contract)."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, seconds: float, derived: Dict):
        derived_s = ";".join(f"{k}={v}" for k, v in derived.items())
        self.rows.append((name, seconds * 1e6, derived_s))
        print(f"{name},{seconds * 1e6:.1f},{derived_s}", flush=True)

    def header(self):
        print("name,us_per_call,derived", flush=True)
