"""Width-bucketed fused ESTIMATE (DESIGN.md SS7 phase C): bucket invariance,
kernel-vs-jnp parity, linf/l1 fused-vs-host parity, and shared-operand
lanes vs solo runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core import estimators
from repro.core.extensions import run_lpmiss, run_maxmiss
from repro.core.fused import (FusedResult, _bucket_widths, fused_l2miss,
                              fused_l2miss_lanes)
from repro.core.l2miss import MissConfig, exact_answer
from repro.data import make_grouped

KW = dict(est_name="avg", B=100, n_min=300, n_max=600, l=6, max_iters=16,
          n_cap=1 << 13, ext_cap=1 << 10)


@pytest.fixture(scope="module")
def data():
    return make_grouped(["normal", "exp"], 60_000, seed=1, biases=[5.0, 3.0])


def _run(data, *, key=3, eps=0.1, **over):
    kw = {**KW, **over}
    return fused_l2miss(
        data.values, jnp.asarray(data.offsets), jnp.ones(2, jnp.float32),
        jax.random.PRNGKey(key), jnp.float32(eps), 0.05, **kw)


def test_bucket_ladder_static():
    assert _bucket_widths(1 << 13, 256) == (256, 512, 1024, 2048, 4096, 8192)
    assert _bucket_widths(1 << 13, 1024) == (1024, 2048, 4096, 8192)
    # Non-power-of-two caps are topped by the cap itself.
    assert _bucket_widths(5000, 1024) == (1024, 2048, 4096, 5000)
    # Ladder length bounds the per-program branch count by ~log2(n_cap).
    assert len(_bucket_widths(1 << 16, 256)) == 9


def test_bucketed_matches_fullwidth(data):
    """Counter-PRNG draws are width-invariant: the bucketed loop must follow
    the exact same trajectory as the full-width (phase B) loop -- identical
    sizes, identical rows gathered; (e, theta) equal up to f32 reduction
    order over the appended zero rows."""
    r_b = _run(data, adaptive=True)
    r_f = _run(data, adaptive=False)
    assert bool(r_b.success) and bool(r_f.success)
    assert np.array_equal(np.asarray(r_b.n), np.asarray(r_f.n))
    assert int(r_b.rows_sampled) == int(r_f.rows_sampled)
    assert int(r_b.iterations) == int(r_f.iterations)
    assert_allclose(float(r_b.error), float(r_f.error), rtol=1e-4)
    assert_allclose(np.asarray(r_b.theta), np.asarray(r_f.theta), rtol=1e-5)


def test_ncap_invariance(data):
    """Growing the capacity (and hence the bucket ladder) must not change
    which rows are gathered nor the answer: the slot->row binding and the
    bootstrap draws depend on absolute slot indices, never on n_cap, as long
    as the trajectory stays below both caps."""
    r_small = _run(data, eps=0.15, n_cap=1 << 12, ext_cap=1 << 10)
    r_large = _run(data, eps=0.15, n_cap=1 << 13, ext_cap=1 << 10)
    assert bool(r_small.success) and bool(r_large.success)
    assert np.array_equal(np.asarray(r_small.n), np.asarray(r_large.n))
    assert int(r_small.rows_sampled) == int(r_large.rows_sampled)
    assert_allclose(float(r_small.error), float(r_large.error), rtol=1e-4)


def test_gated_gather_invariance(data):
    """Phase-E extension-gather gating: wrapping the per-lane window gather
    in lax.cond must not change ONE BIT of the trajectory -- an inactive
    lane's window degenerates to its resident prefix, so the gather it
    skips would have scattered nothing."""
    r_g = _run(data, gate_gather=True)
    r_u = _run(data, gate_gather=False)
    assert bool(r_g.success)
    assert np.array_equal(np.asarray(r_g.n), np.asarray(r_u.n))
    assert int(r_g.rows_sampled) == int(r_u.rows_sampled)
    assert int(r_g.iterations) == int(r_u.iterations)
    assert float(r_g.error) == float(r_u.error)
    assert np.array_equal(np.asarray(r_g.theta), np.asarray(r_u.theta))
    assert np.array_equal(np.asarray(r_g.profile_e), np.asarray(r_u.profile_e))


def test_gated_gather_rows_accounting(data):
    """In the gated path ``rows_sampled`` must still equal the final filled
    watermark exactly: only ACTIVE ticks gather, and each gathers exactly
    its window's worth of new rows."""
    from repro.core.fused import (as_columns, fused_step, init_lane_state,
                                  lane_active, lanes_result,
                                  make_lane_params)

    q = 3
    keys = jax.random.split(jax.random.PRNGKey(5), q)
    eps = jnp.asarray([0.15, 0.08, 0.25], jnp.float32)
    deltas = jnp.full((q,), 0.05, jnp.float32)
    offsets = jnp.asarray(data.offsets)
    kw = {**KW}
    params = make_lane_params(offsets, jnp.ones((q, 2), jnp.float32), keys,
                              eps, deltas, jax.random.PRNGKey(8),
                              n_cap=KW["n_cap"])
    state = init_lane_state(keys, 2, n_cap=KW["n_cap"], c_dim=1, p_dim=1,
                            n_min=KW["n_min"], max_iters=KW["max_iters"],
                            dtype=data.values.dtype)
    cols = as_columns(data.values)
    while bool(np.any(np.asarray(lane_active(state, KW["max_iters"])))):
        state = fused_step(cols, offsets, state, params, gate_gather=True,
                           **kw)
    res = lanes_result(state)
    assert np.array_equal(np.asarray(res.rows_sampled),
                          np.asarray(state.filled).sum(axis=1))
    assert bool(np.all(np.asarray(res.success)))


def test_kernel_interpret_matches_jnp(data):
    """use_kernel routes ESTIMATE through the Pallas kernel (interpret mode
    on CPU); it consumes the SAME counter stream as the jnp path, so the
    whole MISS trajectory matches bit-for-bit, not just statistically."""
    r_k = _run(data, use_kernel=True)
    r_j = _run(data, use_kernel=False)
    assert np.array_equal(np.asarray(r_k.n), np.asarray(r_j.n))
    assert int(r_k.rows_sampled) == int(r_j.rows_sampled)
    assert_allclose(float(r_k.error), float(r_j.error), rtol=1e-5)
    assert_allclose(np.asarray(r_k.theta), np.asarray(r_j.theta), rtol=1e-5)


@pytest.mark.parametrize("metric,host_runner", [
    ("linf", lambda d, cfg: run_maxmiss(d, "avg", cfg)),
    ("l1", lambda d, cfg: run_lpmiss(d, "avg", cfg, p=1)),
])
def test_fused_metric_matches_host(data, metric, host_runner):
    """Host-loop-vs-fused parity for the linf/l1 metric extensions: both
    converge under the bound with final sizes in the same ballpark (exact
    draw equality is impossible across the two sampling substrates)."""
    eps = 0.08
    res = _run(data, eps=eps, metric=metric)
    assert bool(res.success)
    assert float(res.error) <= eps
    tr = host_runner(data, MissConfig(
        epsilon=eps, delta=0.05, B=100, n_min=300, n_max=600, l=6, seed=0,
        max_iters=30))
    assert tr.success
    ratio = float(np.sum(np.asarray(res.n))) / max(tr.total_sample_size, 1)
    assert 0.1 < ratio < 10.0
    # Both honour the bound against the exact answer up to noise.
    truth = exact_answer(data, estimators.get("avg")).ravel()
    dev = np.abs(np.asarray(res.theta).ravel() - truth)
    joint = dev.max() if metric == "linf" else dev.sum()
    assert joint <= 2 * eps


def test_shared_operand_batch_matches_solo(data):
    """Shared-operand lanes (2D values): each lane's trajectory must be
    bit-identical to running it alone with the same keys -- the shared width
    bucket (max over active lanes) is statistically invisible."""
    q = 3
    keys = jax.random.split(jax.random.PRNGKey(1), q)
    eps = jnp.asarray([0.15, 0.08, 0.2], jnp.float32)
    skey = jax.random.PRNGKey(7)
    rb = fused_l2miss_lanes(
        data.values, jnp.asarray(data.offsets), jnp.ones((q, 2), jnp.float32),
        keys, eps, jnp.full((q,), 0.05, jnp.float32), sample_keys=skey, **KW)
    assert isinstance(rb, FusedResult)
    assert bool(np.all(np.asarray(rb.success)))
    totals = np.asarray(rb.n).sum(axis=1)
    assert totals[1] >= totals[0] and totals[1] >= totals[2]
    for lane in range(q):
        rs = fused_l2miss(
            data.values, jnp.asarray(data.offsets), jnp.ones(2, jnp.float32),
            keys[lane], eps[lane], 0.05, sample_key=skey, **KW)
        assert np.array_equal(np.asarray(rs.n), np.asarray(rb.n)[lane])
        assert int(rs.rows_sampled) == int(np.asarray(rb.rows_sampled)[lane])
        assert_allclose(float(rs.error), float(np.asarray(rb.error)[lane]),
                        rtol=1e-5)


def test_batch_per_lane_deltas(data):
    """delta may vary per lane (per-query confidence in one dispatch)."""
    q = 2
    keys = jax.random.split(jax.random.PRNGKey(2), q)
    eps = jnp.asarray([0.15, 0.15], jnp.float32)
    res = fused_l2miss_lanes(
        data.values, jnp.asarray(data.offsets), jnp.ones((q, 2), jnp.float32),
        keys, eps, jnp.asarray([0.05, 0.2], jnp.float32),
        sample_keys=jax.random.PRNGKey(9), **KW)
    assert bool(np.all(np.asarray(res.success)))


def test_legacy_batch_shared_sample_key(data):
    """One shared (2,) sample key binds every lane to the same slot table:
    the answers equal those with that key tiled per lane, (q, 2)."""
    q = 2
    keys = jax.random.split(jax.random.PRNGKey(4), q)
    eps = jnp.asarray([0.15, 0.2], jnp.float32)
    deltas = jnp.full((q,), 0.05, jnp.float32)
    skey = jax.random.PRNGKey(7)
    r_shared = fused_l2miss_lanes(
        data.values, jnp.asarray(data.offsets), jnp.ones((q, 2), jnp.float32),
        keys, eps, deltas, sample_keys=skey, **KW)
    r_tiled = fused_l2miss_lanes(
        data.values, jnp.asarray(data.offsets), jnp.ones((q, 2), jnp.float32),
        keys, eps, deltas,
        sample_keys=jnp.broadcast_to(skey, (q,) + skey.shape), **KW)
    assert bool(np.all(np.asarray(r_shared.success)))
    assert np.array_equal(np.asarray(r_shared.n), np.asarray(r_tiled.n))
    assert_allclose(np.asarray(r_shared.error), np.asarray(r_tiled.error))


def test_resolve_use_kernel_auto_cpu():
    from repro.kernels import resolve_use_kernel
    import jax as _jax

    want = _jax.default_backend() == "tpu"
    assert resolve_use_kernel("auto") == want
    assert resolve_use_kernel(True) is True
    assert resolve_use_kernel(False) is False
    with pytest.raises(ValueError):
        resolve_use_kernel("maybe")
