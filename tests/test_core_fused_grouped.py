"""Grouped lane blocks (DESIGN.md phase I): fused_grouped vs G solo runs.

The tentpole contract: a grouped query admitted as ONE shared-scan block of
G per-group lanes must reproduce G solo ``fused_l2miss`` runs on the group
slices -- same keys (``fold_in(query_key, g)``), same sample bindings
(``stratum_key(sample_key, g)``), same statics.  Trajectory integers
(sizes, iterations, verdicts, rows) are EXACT; ``theta`` agrees to f32
vmap-order noise (rtol 1e-5); the bootstrap error quantile agrees to rtol
1e-3 -- the documented tolerance: the segment pass sums each replicate in
packed-stream order, the solo path in per-lane order, and the ~1e-4
absolute f32 difference on sums of n terms is amplified by the small
|theta_b - theta| deviations the quantile is taken over.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core import fused, sampling

SPEC = dict(B=64, n_min=100, n_max=200, l=4, max_iters=12, n_cap=1 << 11,
            ext_cap=1 << 9)
EPS, DELTA = 0.25, 0.05


def _make(G=8, seed=0, sizes=None):
    rng = np.random.default_rng(seed)
    if sizes is None:
        sizes = rng.integers(400, 3000, size=G)
    sizes = np.asarray(sizes)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    vals = np.empty((int(offsets[-1]), 1), np.float32)
    for g in range(len(sizes)):
        vals[offsets[g]:offsets[g + 1], 0] = rng.normal(
            rng.normal(5.0, 2.0), rng.uniform(0.5, 1.5), size=sizes[g])
    return jnp.asarray(vals), offsets, sizes


def _solo(values, offsets, sizes, key, g, **over):
    spec = {**SPEC, **over}
    return jax.tree.map(np.asarray, fused.fused_l2miss(
        values[offsets[g]:offsets[g + 1]],
        jnp.asarray([0, int(sizes[g])]), np.ones(1),
        jax.random.fold_in(key, g), EPS, DELTA,
        sample_key=sampling.stratum_key(key, g), est_name="avg", **spec))


def test_block_matches_solo_runs():
    values, offsets, sizes = _make()
    key = jax.random.PRNGKey(42)
    blk = jax.tree.map(np.asarray, fused.fused_grouped(
        values, jnp.asarray(offsets), np.ones(len(sizes)), key, EPS, DELTA,
        est_name="avg", **SPEC))
    for g in range(len(sizes)):
        solo = _solo(values, offsets, sizes, key, g)
        assert int(blk.n[g]) == int(solo.n[0]), g
        assert int(blk.iterations[g]) == int(solo.iterations), g
        assert bool(blk.success[g]) == bool(solo.success), g
        assert int(blk.rows_sampled[g]) == int(solo.rows_sampled), g
        assert_allclose(blk.theta[g], solo.theta[0], rtol=1e-5)
        assert_allclose(blk.error[g], solo.error, rtol=1e-3)


def _rung(widths, v):
    return int(next((w for w in widths if v <= w), widths[-1]))


def test_block_scans_fewer_rows_than_solo_lanes():
    """One grouped block scans fewer ESTIMATE rows than G solo lanes for
    the same query.  Each side is priced at the rungs its compiled step
    runs, over active iterations only: the block one ``seg_ladder`` rung
    over the packed sum of active prefixes per tick, a solo lane one
    ``bucket_ladder`` rung per iteration."""
    values, offsets, sizes = _make()
    G = len(sizes)
    key = jax.random.PRNGKey(42)
    blk = jax.tree.map(np.asarray, fused.fused_grouped(
        values, jnp.asarray(offsets), np.ones(G), key, EPS, DELTA,
        est_name="avg", **SPEC))
    solos = [_solo(values, offsets, sizes, key, g) for g in range(G)]

    seg_w = fused.seg_ladder(
        fused.grouped_seg_cap(offsets, SPEC["n_cap"]), SPEC["n_max"])
    rows_block = sum(
        _rung(seg_w, int(sum(blk.profile_n[g, t] for g in range(G)
                             if t < blk.iterations[g])))
        for t in range(int(blk.iterations.max())))
    buck_w = fused.bucket_ladder(SPEC["n_cap"], SPEC["n_max"])
    rows_solo = sum(_rung(buck_w, int(s.profile_n[t, 0]))
                    for s in solos for t in range(int(s.iterations)))
    assert 0 < rows_block < rows_solo


def test_block_kernel_path_matches_jnp_path():
    """use_kernel routes ESTIMATE through segment_bootstrap_moments (the
    Pallas kernel; interpret off-TPU).  Trajectories must agree with the
    jnp segment path: the kernel's tile loop IS the reference summation
    order (ref.py mirrors it), so sizes match exactly and moments to f32
    noise."""
    values, offsets, sizes = _make(G=4, seed=3)
    key = jax.random.PRNGKey(7)
    a = jax.tree.map(np.asarray, fused.fused_grouped(
        values, jnp.asarray(offsets), np.ones(len(sizes)), key, EPS, DELTA,
        est_name="avg", use_kernel=False, **SPEC))
    b = jax.tree.map(np.asarray, fused.fused_grouped(
        values, jnp.asarray(offsets), np.ones(len(sizes)), key, EPS, DELTA,
        est_name="avg", use_kernel=True, **SPEC))
    assert np.array_equal(a.n, b.n)
    assert np.array_equal(a.iterations, b.iterations)
    assert np.array_equal(a.success, b.success)
    assert_allclose(a.theta, b.theta, rtol=1e-4)
    assert_allclose(a.error, b.error, rtol=1e-3)


def test_per_group_contracts_on_zipf_mix():
    """Rare-group guarantee: under a Zipfian size mix the smallest stratum
    still meets its OWN (eps, delta) bound -- stratified prefixes mean rare
    groups extend their own streams instead of starving under the head."""
    G = 10
    raw = 6000 / (np.arange(1, G + 1) ** 1.2)
    sizes = np.maximum(raw.astype(np.int64), 500)
    values, offsets, sizes = _make(G=G, seed=11, sizes=sizes)
    blk = jax.tree.map(np.asarray, fused.fused_grouped(
        values, jnp.asarray(offsets), np.ones(G), jax.random.PRNGKey(5),
        EPS, DELTA, est_name="avg", **SPEC))
    assert bool(blk.success.all()), blk.error
    assert (blk.error <= EPS).all()
    # Per-group exactness: each answer is close to ITS group's true mean.
    for g in range(G):
        truth = float(np.asarray(values)[offsets[g]:offsets[g + 1]].mean())
        assert abs(float(blk.theta[g, 0]) - truth) <= 3 * EPS, g
    # The rare tail converged on its own stratum, not on head spillover.
    assert int(blk.n[-1]) <= int(sizes[-1])


def test_per_group_epsilon_rows():
    """A (G,) epsilon vector gives every group its own clause: tight groups
    sample more than loose ones on the same data."""
    values, offsets, sizes = _make(G=4, seed=9,
                                   sizes=np.full(4, 2000, np.int64))
    eps = np.array([0.1, 0.5, 0.1, 0.5], np.float32)
    blk = jax.tree.map(np.asarray, fused.fused_grouped(
        values, jnp.asarray(offsets), np.ones(4), jax.random.PRNGKey(1),
        eps, DELTA, est_name="avg", **SPEC))
    assert bool(blk.success.all())
    assert (blk.error <= eps).all()
    assert int(blk.n[0]) >= int(blk.n[1])
    assert int(blk.n[2]) >= int(blk.n[3])


def test_grouped_seg_cap_and_ladder():
    off = np.array([0, 100, 5000], np.int64)
    cap = fused.grouped_seg_cap(off, 1 << 11)
    assert cap == 100 + min(4900, 1 << 11)
    rungs = fused.seg_ladder(cap, 200)
    assert rungs[-1] == cap
    assert all(a < b for a, b in zip(rungs, rungs[1:]))


def test_engine_routes_group_by():
    """AQPEngine.execute sends group_by queries through the block path and
    returns per-group verdicts."""
    from repro.aqp.engine import AQPEngine
    from repro.aqp.query import Query
    from repro.core.sampling import GroupedData

    values, offsets, sizes = _make(G=5, seed=21)
    data = GroupedData(np.asarray(values), offsets)
    eng = AQPEngine(data, B=64, n_min=100, n_max=200, use_kernel=False)
    res = eng.execute(Query(func="avg", epsilon=EPS, delta=DELTA,
                            group_by=True))
    succ = np.asarray(res.success)
    assert succ.shape == (5,)
    assert bool(succ.all())
    exact = np.asarray(eng.exact(Query(func="avg", epsilon=EPS)))
    assert_allclose(np.asarray(res.theta)[:, 0], exact[:, 0], atol=3 * EPS)


def test_engine_grouped_rejects_non_moment_metric():
    from repro.aqp.engine import AQPEngine
    from repro.aqp.query import Query
    from repro.core.sampling import GroupedData

    values, offsets, sizes = _make(G=3, seed=2)
    data = GroupedData(np.asarray(values), offsets)
    eng = AQPEngine(data, B=64, n_min=100, n_max=200)
    with pytest.raises(ValueError):
        eng.execute(Query(func="avg", epsilon=0.1, metric="linf",
                          group_by=True))
