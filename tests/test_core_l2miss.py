"""End-to-end L2Miss (Algorithm 3) behaviour: convergence, accuracy
(simulated confidence, paper SS6.1), efficiency (near-optimal sizes vs the
CLT oracle), failure diagnostics, and the fused on-device variant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import estimators
from repro.core.fused import fused_l2miss
from repro.core.l2miss import MissConfig, exact_answer, run_l2miss
from repro.data import make_grouped

EPS = 0.05
CFG = dict(delta=0.05, B=150, n_min=400, n_max=800, l=6, seed=0, max_iters=40)


@pytest.fixture(scope="module")
def normal_exp_data():
    return make_grouped(["normal", "exp"], 150_000, seed=1, biases=[5.0, 3.0])


def test_l2miss_converges(normal_exp_data):
    tr = run_l2miss(normal_exp_data, "avg", MissConfig(epsilon=EPS, **CFG))
    assert tr.success, tr.status
    assert tr.error <= EPS
    # With a wide eps the run accepts after 1-2 prediction points, so r2 is
    # pure noise here (historically flaky at ~0.2); the fit-quality floor
    # lives in test_l2miss_near_oracle_size where the profile is long enough
    # for r2 to be meaningful.  Here just require the fit to exist.
    assert "r2" in tr.info
    truth = exact_answer(normal_exp_data, estimators.get("avg")).ravel()
    actual = float(np.sqrt(np.sum((tr.theta.ravel() - truth) ** 2)))
    assert actual <= 2 * EPS  # estimate honours the bound up to noise
    # Incremental SampleStore accounting: rows actually gathered (delta) is
    # what total_sampled reports, and it never exceeds fresh-resample cost.
    assert tr.total_sampled == tr.info["rows_touched"]
    assert tr.total_sampled <= int(tr.profile_n.sum())


def test_l2miss_near_oracle_size(normal_exp_data):
    """Total size within a small factor of the CLT closed form (BLK oracle)."""
    tr = run_l2miss(normal_exp_data, "avg", MissConfig(epsilon=0.02, **CFG))
    assert tr.success
    # Tight eps -> long profile -> the WLS fit must actually explain it.
    assert tr.info["r2"] > 0.9
    # Oracle: per-group n = (z_{.975} sigma sqrt(2)/eps)^2, sigma = 1 for both
    # normal(5,1) and exp(1)+3 groups.
    z = 1.96
    oracle = 2 * (z * 1.0 * np.sqrt(2) / 0.02) ** 2
    assert tr.total_sample_size < 4 * oracle
    assert tr.total_sample_size > oracle / 4
    # >= 3 iterations of growth: nested sampling must touch strictly fewer
    # rows than redrawing every iteration from scratch.
    assert tr.iterations >= 3
    assert tr.total_sampled < int(tr.profile_n.sum())


@pytest.mark.slow
def test_l2miss_simulated_confidence(normal_exp_data):
    """Paper SS6.1: resample at the returned size; the fraction of trials
    meeting the bound must be >= 1 - delta (up to MC noise)."""
    data = normal_exp_data
    tr = run_l2miss(data, "avg", MissConfig(epsilon=EPS, **CFG))
    assert tr.success
    truth = exact_answer(data, estimators.get("avg")).ravel()
    est = estimators.get("avg")
    from repro.core.sampling import bucket_cap, stratified_sample

    n_cap = bucket_cap(int(tr.n.max()))
    n_vec = jnp.asarray(tr.n)
    offs = jnp.asarray(data.offsets)

    @jax.jit
    def one(key):
        sample, mask = stratified_sample(key, data.values, offs, n_vec, n_cap)
        th = jax.vmap(lambda xg, mg: est.apply(est.prepare(xg), mg))(sample, mask)
        return jnp.sqrt(jnp.sum((th[:, 0] - jnp.asarray(truth)) ** 2))

    trials = 60
    keys = jax.random.split(jax.random.PRNGKey(42), trials)
    errs = np.asarray(jax.vmap(one)(keys))
    conf = float((errs <= EPS).mean())
    assert conf >= 0.85, f"simulated confidence {conf}"


def test_l2miss_sum_query(normal_exp_data):
    data = normal_exp_data
    scale = float(data.scale[0])
    eps_sum = 0.01 * 5.0 * scale  # 1% relative on group-0 SUM
    tr = run_l2miss(data, "sum", MissConfig(epsilon=eps_sum, **CFG))
    assert tr.success
    truth = exact_answer(data, estimators.get("sum")).ravel()
    err = float(np.sqrt(np.sum((tr.theta.ravel() - truth) ** 2)))
    assert err <= 2 * eps_sum


@pytest.mark.slow
def test_l2miss_median(normal_exp_data):
    tr = run_l2miss(normal_exp_data, "median", MissConfig(epsilon=EPS, **CFG))
    assert tr.success
    truth = exact_answer(normal_exp_data, estimators.get("median")).ravel()
    err = float(np.sqrt(np.sum((tr.theta.ravel() - truth) ** 2)))
    assert err <= 2 * EPS


def test_growth_guard_monotone(normal_exp_data):
    """Lemma 5 (as enforced): per-group sizes never shrink in prediction."""
    tr = run_l2miss(normal_exp_data, "avg", MissConfig(epsilon=0.02, **CFG))
    l = 6
    pn = tr.profile_n[l:]
    assert np.all(np.diff(pn, axis=0) >= 0)


def test_budget_failure(normal_exp_data):
    cfg = MissConfig(epsilon=1e-6, budget_rows=20_000, **CFG)
    tr = run_l2miss(normal_exp_data, "avg", cfg)
    assert not tr.success
    assert tr.status == "budget"


@pytest.mark.slow
def test_unrecoverable_constant_error():
    """A degenerate profile (error independent of n) must trip Algorithm 2."""
    rng = np.random.default_rng(0)
    # Cauchy-like data via pareto1: AVG is not consistent -> error stalls.
    from repro.data import make_single_group

    data = make_single_group("pareto1", 200_000, seed=3)
    cfg = MissConfig(epsilon=1e-4, delta=0.05, B=100, n_min=200, n_max=400,
                     l=6, seed=0, max_iters=12, tau=0.02,
                     budget_rows=3_000_000)
    tr = run_l2miss(data, "avg", cfg)
    # Any of the failure paths is acceptable; success at 1e-4 on pareto1 isn't.
    assert tr.status in ("unrecoverable", "budget", "max_iters")


def test_fused_matches_host(normal_exp_data):
    """Golden-trace contract for the nested-sample fused path: exact draw
    equality with the host loop is impossible (permuted-prefix vs host store
    RNG), so pin convergence status and final size agreement instead."""
    data = normal_exp_data
    res = fused_l2miss(
        data.values, jnp.asarray(data.offsets), jnp.ones(2, jnp.float32),
        jax.random.PRNGKey(0), jnp.float32(EPS), 0.05,
        est_name="avg", B=150, n_min=400, n_max=800, l=6,
        max_iters=24, n_cap=1 << 14)
    assert bool(res.success)
    assert float(res.error) <= EPS
    tr = run_l2miss(data, "avg", MissConfig(epsilon=EPS, **CFG))
    assert tr.success
    # Same problem, same config family: sizes agree within a small factor.
    ratio = float(np.sum(np.asarray(res.n))) / max(tr.total_sample_size, 1)
    assert 0.1 < ratio < 10.0
    # Carried-buffer accounting: the fused loop gathers each slot once, so
    # total rows sampled is the final filled watermark (>= final n because
    # the stacked init windows are part of the prefix).
    assert int(res.rows_sampled) >= int(np.asarray(res.n).sum())
    assert int(res.rows_sampled) <= int(np.asarray(res.profile_n).sum())


def test_fused_deterministic_given_keys(normal_exp_data):
    """Same keys -> identical trace (the nested sample path is a pure
    function of (sample_key, bootstrap key); nothing is order-dependent)."""
    data = normal_exp_data
    kw = dict(est_name="avg", B=100, n_min=400, n_max=800, l=6,
              max_iters=16, n_cap=1 << 13)
    args = (data.values, jnp.asarray(data.offsets), jnp.ones(2, jnp.float32),
            jax.random.PRNGKey(3), jnp.float32(EPS), 0.05)
    r1 = fused_l2miss(*args, **kw)
    r2 = fused_l2miss(*args, **kw)
    assert np.array_equal(np.asarray(r1.n), np.asarray(r2.n))
    assert float(r1.error) == float(r2.error)
    # Passing sample_key == key explicitly is the same program as the
    # default (sample_key=None folds in the main key).
    r3 = fused_l2miss(*args, jax.random.PRNGKey(3), **kw)
    assert np.array_equal(np.asarray(r1.n), np.asarray(r3.n))
    assert float(r1.error) == float(r3.error)


def test_fused_batch_vmap(normal_exp_data):
    """Lanes of one closed loop: a tighter epsilon draws more rows."""
    from repro.core.fused import fused_l2miss_lanes

    data = normal_exp_data
    q = 3
    scales = jnp.ones((q, 2), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(1), q)
    eps = jnp.asarray([0.1, 0.05, 0.2], jnp.float32)
    deltas = jnp.full((q,), 0.05, jnp.float32)
    spec = dict(est_name="avg", B=100, n_min=400, n_max=800, l=6,
                max_iters=16, n_cap=1 << 13)
    res = fused_l2miss_lanes(
        data.values, jnp.asarray(data.offsets), scales, keys, eps, deltas,
        **spec)
    assert bool(np.all(np.asarray(res.success)))
    # Tighter eps -> more samples.
    totals = np.asarray(res.n).sum(axis=1)
    assert totals[1] >= totals[0] >= totals[2]
    # Shared-prefix variant: one sample key for every lane.
    res2 = fused_l2miss_lanes(
        data.values, jnp.asarray(data.offsets), scales, keys, eps, deltas,
        jax.random.PRNGKey(7), **spec)
    assert bool(np.all(np.asarray(res2.success)))
    totals2 = np.asarray(res2.n).sum(axis=1)
    assert totals2[1] >= totals2[0] >= totals2[2]
