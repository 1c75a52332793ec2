"""The linear error model H(n; beta) = beta0 - sum_i beta_i log n_i (paper SS2.2)
with WLS fitting (Eq. 11), failure diagnostic (Alg. 2) and the closed-form
Lagrange prediction of the optimal sample size (Eq. 13).

Everything here is pure jnp and jit/vmap-friendly: the fused on-device MISS
loop (core/fused.py) reuses these functions inside ``lax.while_loop``, and the
host L2Miss loop (core/l2miss.py) calls them per iteration.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# Diagnostic status codes (Algorithm 2).
DIAG_OK = 0
DIAG_RECOVERED = 1      # some beta_i <= 0 -> equalized (recoverable failure)
DIAG_FAILURE = 2        # sum beta_i <= tau -> unrecoverable


class ErrorModelFit(NamedTuple):
    beta: Array        # (m + 1,): [beta0, beta_1..beta_m]
    r2: Array          # scalar goodness of fit on the weighted profile
    status: Array      # int32 diagnostic code


def design_row(n_vec: Array) -> Array:
    """n-tilde = (1, -log n_1, ..., -log n_m)."""
    return jnp.concatenate([jnp.ones((1,), n_vec.dtype if jnp.issubdtype(
        n_vec.dtype, jnp.floating) else jnp.float32),
        -jnp.log(n_vec.astype(jnp.float32))])


def fit_wls(
    profile_n: Array,      # (k, m) sample sizes, rows may be padding
    profile_loge: Array,   # (k,) log estimated errors
    row_valid: Array,      # (k,) 1.0 for real observations, 0.0 padding
) -> Tuple[Array, Array]:
    """Weighted least squares fit of H (Eq. 11), w_k = total sample size C(n).

    Returns (beta (m+1,), r2).  Implemented via lstsq on sqrt(W)-scaled rows
    for numerical stability; padding rows get zero weight so a single fixed
    (k, m) buffer serves the whole MISS run on device.
    """
    k, m = profile_n.shape
    ones = jnp.ones((k, 1), jnp.float32)
    N = jnp.concatenate([ones, -jnp.log(profile_n.astype(jnp.float32))], axis=1)
    w = jnp.sum(profile_n, axis=1).astype(jnp.float32) * row_valid  # w_k = C(n)
    sw = jnp.sqrt(w)
    A = N * sw[:, None]
    y = profile_loge * sw
    # Ridge-stabilized normal equations (k can be < m+1 early on; the ridge
    # keeps the solve well-posed and the init phase guarantees k >= m+1
    # before predictions are used).
    # Full-f32 products: the log-size columns are nearly collinear with the
    # intercept, and a TPU's default matmul precision (bf16 operands) turns
    # the normal equations into noise.
    mm = partial(jnp.matmul, precision="highest")
    G = mm(A.T, A) + 1e-8 * jnp.eye(m + 1, dtype=jnp.float32)
    beta = jnp.linalg.solve(G, mm(A.T, y))
    # Weighted r^2.
    resid = (mm(N, beta) - profile_loge) * sw
    mean_y = jnp.sum(w * profile_loge) / jnp.maximum(jnp.sum(w), 1e-12)
    ss_res = jnp.sum(resid**2)
    ss_tot = jnp.sum(w * (profile_loge - mean_y) ** 2)
    r2 = 1.0 - ss_res / jnp.maximum(ss_tot, 1e-12)
    return beta, r2


def diagnose(beta: Array, tau: float) -> Tuple[Array, Array]:
    """Algorithm 2.  Returns (calibrated beta, status code).

    Unrecoverable: sum_i beta_i <= tau  (error will not shrink with n).
    Recoverable:   min_i beta_i <= 0    -> equalize the slopes to their mean.
    """
    slopes = beta[1:]
    total = jnp.sum(slopes)
    unrecoverable = total <= tau
    recoverable = jnp.min(slopes) <= 0.0
    mean_slope = total / slopes.shape[0]
    slopes_fixed = jnp.where(recoverable, jnp.full_like(slopes, mean_slope), slopes)
    beta_out = jnp.concatenate([beta[:1], slopes_fixed])
    status = jnp.where(
        unrecoverable, DIAG_FAILURE, jnp.where(recoverable, DIAG_RECOVERED, DIAG_OK)
    ).astype(jnp.int32)
    return beta_out, status


def predict_optimal_n(beta: Array, log_eps: Array,
                      cost_weights: Array | None = None) -> Array:
    """Closed-form solution of  min c'n  s.t.  H(n; beta) <= log eps.

    Uniform cost (Eq. 13): n_i = beta_i * exp((beta0 - sum_j beta_j
    log beta_j - log eps) / sum_j beta_j).

    Non-uniform linear cost c (paper SS8 "non-uniformly linear" extension):
    stationarity gives c_i = lambda beta_i / n_i, so n_i = lambda beta_i /
    c_i and  log lambda = (beta0 - sum_j beta_j log(beta_j / c_j) - log eps)
    / sum_j beta_j.

    Assumes all slopes positive (guaranteed post-diagnose unless FAILURE).
    """
    b0, b = beta[0], beta[1:]
    b = jnp.maximum(b, 1e-9)
    s = jnp.sum(b)
    if cost_weights is None:
        ratio = b
    else:
        ratio = b / jnp.maximum(cost_weights, 1e-12)
    log_lambda = (b0 - jnp.sum(b * jnp.log(ratio)) - log_eps) / s
    n_hat = ratio * jnp.exp(log_lambda)
    return n_hat


def model_value(beta: Array, n_vec: Array) -> Array:
    """H(n; beta) = beta0 - sum_i beta_i log n_i (predicted log error)."""
    return beta[0] - jnp.sum(beta[1:] * jnp.log(n_vec.astype(jnp.float32)))


def fit_and_predict(
    profile_n: Array,
    profile_loge: Array,
    row_valid: Array,
    log_eps: Array,
    tau: float,
    cost_weights: Array | None = None,
) -> Tuple[Array, ErrorModelFit]:
    """Fused PREDICT subroutine: fit -> diagnose -> closed-form optimum."""
    beta, r2 = fit_wls(profile_n, profile_loge, row_valid)
    beta_cal, status = diagnose(beta, tau)
    n_hat = predict_optimal_n(beta_cal, log_eps, cost_weights)
    return n_hat, ErrorModelFit(beta_cal, r2, status)
