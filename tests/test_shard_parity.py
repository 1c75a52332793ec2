"""Phase-G sharding (DESIGN.md SS G): the multi-device lane pool's
determinism contract and the host-side layout invariants it rests on.

The load-bearing invariants:

  * ``ShardLayout.alloc`` is the identity at S=1, 1-Lipschitz per step, and
    partitions every logical prefix exactly across shards -- the growth
    clamp and the segment fills are built on those three properties;
  * sharded slot tables only ever bind slots to rows INSIDE their shard's
    sub-extent, so zero-padded rows can never be gathered;
  * the windowed ESTIMATE's mask is exact: slots outside a lane's live
    window contribute bit-zero regardless of buffer contents, and the rung
    a window lands on never changes its sums;
  * a solo sharded ``fused_l2miss`` converges under 2- and 4-way layouts;
  * the mesh pool drains BIT-equal to the mesh=False pool of the same
    layout (needs >= 2 host devices; skipped in single-device runs), and
    pooled answers match per-query solo references at the lane-count
    compile tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core import bootstrap, estimators
from repro.core import mesh as core_mesh
from repro.core.fused import fused_l2miss, resolve_seg_window, _window_ladder
from repro.core.sampling import ShardLayout, sharded_slot_tables
from repro.data import make_grouped
from repro.kernels import prng

SPEC = dict(B=60, n_min=100, n_max=256, max_iters=8, n_cap=1 << 10)


@pytest.fixture(scope="module")
def data():
    return make_grouped(["normal", "exp"], 12_000, seed=3, biases=[4.0, 2.0])


# ---------------------------------------------------------------------------
# ShardLayout: the alloc-table contract
# ---------------------------------------------------------------------------

def test_shard_layout_invariants(data):
    offsets = np.asarray(data.offsets)
    sizes = np.diff(offsets)
    for S in (1, 2, 4):
        lay = ShardLayout.build(offsets, n_cap=SPEC["n_cap"], num_shards=S)
        alloc = lay.alloc.astype(np.int64)
        # 1-Lipschitz: each shard gains at most one slot per logical slot.
        d = np.diff(alloc, axis=2)
        assert d.min() >= 0 and d.max() <= 1
        # Exact partition: every logical prefix splits across shards with
        # nothing lost and nothing double-counted.
        tot = alloc.sum(axis=0)                        # (m, n_cap+1)
        for i, cg in enumerate(lay.cap_groups):
            n = np.arange(SPEC["n_cap"] + 1)
            expect = np.minimum(n, alloc[:, i, -1].sum())
            np.testing.assert_array_equal(tot[i], expect)
        if S == 1:
            # Identity: one shard owns every slot.
            for i in range(len(sizes)):
                cap_i = alloc[0, i, -1]
                np.testing.assert_array_equal(
                    alloc[0, i], np.minimum(np.arange(SPEC["n_cap"] + 1),
                                            cap_i))
        # Row accounting matches the block partition of the table.
        assert lay.lsizes.sum() == offsets[-1]


def test_sharded_slot_tables_stay_inside_sub_extents(data):
    """No slot may bind a padded or foreign row: every table entry lands in
    its shard's own sub-extent of its group (the padded-row mask at the
    binding layer -- rows the alloc table owns are always real rows)."""
    lay = ShardLayout.build(np.asarray(data.offsets), n_cap=SPEC["n_cap"],
                            num_shards=4)
    skey = jax.random.PRNGKey(5)
    local = np.asarray(sharded_slot_tables(skey, lay, local_rows=True))
    glob = np.asarray(sharded_slot_tables(skey, lay, local_rows=False))
    S, m, _ = local.shape
    for s in range(S):
        for i in range(m):
            lo, sz = int(lay.lstarts[s, i]), int(lay.lsizes[s, i])
            if sz == 0:
                continue
            assert local[s, i].min() >= lo
            assert local[s, i].max() < lo + sz
    # Global view is the same binding shifted by the row-block offset.
    shift = (np.arange(S) * lay.rows_per_shard)[:, None, None]
    np.testing.assert_array_equal(glob, local + shift)


def test_window_ladder_and_seg_window():
    for cap, base in ((2048, 150), (1024, 75), (256, 256)):
        ladder = _window_ladder(cap, base)
        assert ladder[-1] == cap
        assert all(a < b for a, b in zip(ladder, ladder[1:]))
        assert ladder[0] <= base
    # The per-segment window is the proportional share of the global
    # extension window (plus slack), never more than the segment capacity.
    for S in (1, 2, 4):
        w = resolve_seg_window(1 << 12, 1 << 9, S)
        assert 0 < w <= (1 << 12) // S
        assert w >= -(-(1 << 9) // S)


# ---------------------------------------------------------------------------
# Windowed ESTIMATE: mask exactness, rung invariance, gating
# ---------------------------------------------------------------------------

def _windowed_case(q=6, m=2, cap=128, B=16, seed=0):
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.normal(size=(q, m, cap)).astype(np.float32))
    lo = jnp.asarray(rng.integers(0, cap // 2, size=(q, m)), jnp.int32)
    width = rng.integers(1, cap // 2, size=(q, m))
    hi = jnp.asarray(np.asarray(lo) + width, jnp.int32)
    seeds = jnp.asarray(rng.integers(0, 2**32, size=(q, m)), jnp.uint32)
    act = jnp.ones((q,), bool)
    return vals, lo, hi, seeds, act


def test_windowed_sums_mask_is_exact():
    """Rows outside [lo, hi) contribute bit-zero: poisoning them with huge
    finite values must not change a single output bit."""
    vals, lo, hi, seeds, act = _windowed_case()
    widths = (64, 128)
    M, Mp = bootstrap.windowed_lane_moment_sums(
        vals, lo, hi, seeds, 16, widths, lane_active=act)
    pos = jnp.arange(vals.shape[2])[None, None, :]
    outside = (pos < lo[..., None]) | (pos >= hi[..., None])
    poisoned = jnp.where(outside, jnp.float32(1e30), vals)
    M2, Mp2 = bootstrap.windowed_lane_moment_sums(
        poisoned, lo, hi, seeds, 16, widths, lane_active=act)
    assert np.asarray(M).tobytes() == np.asarray(M2).tobytes()
    assert np.asarray(Mp).tobytes() == np.asarray(Mp2).tobytes()


def test_windowed_sums_match_direct_reference():
    """The rung gather reproduces the direct full-width contraction: weights
    hash on absolute slot positions, so where the window sits inside the
    gathered slice never reweights a row."""
    vals, lo, hi, seeds, act = _windowed_case()
    q, m, cap = vals.shape
    B = 16
    M, Mp = bootstrap.windowed_lane_moment_sums(
        vals, lo, hi, seeds, B, (32, 64, cap), lane_active=act)
    pos = jnp.arange(cap, dtype=jnp.uint32)
    mf = ((pos[None, None, :] >= lo[..., None])
          & (pos[None, None, :] < hi[..., None])).astype(jnp.float32)
    feats = jnp.stack([mf, mf * vals, mf * vals * vals], axis=-1)
    W = prng.poisson1_weights_at(
        seeds[..., None, None], pos[None, None, :, None],
        jnp.arange(B, dtype=jnp.uint32)[None, None, None, :])
    M_ref = jnp.einsum("qmnb,qmnp->qmbp", W, feats)
    Mp_ref = jnp.sum(feats, axis=2)
    assert_allclose(np.asarray(M), np.asarray(M_ref), rtol=2e-5, atol=1e-5)
    assert_allclose(np.asarray(Mp), np.asarray(Mp_ref), rtol=2e-5,
                    atol=1e-5)


def test_windowed_sums_gate_inactive_lanes():
    vals, lo, hi, seeds, _ = _windowed_case()
    act = jnp.asarray([True, False, True, False, False, False])
    M, Mp = bootstrap.windowed_lane_moment_sums(
        vals, lo, hi, seeds, 16, (64, 128), lane_active=act)
    a = np.asarray(act)
    assert np.all(np.asarray(M)[~a] == 0.0)
    assert np.all(np.asarray(Mp)[~a] == 0.0)
    assert np.any(np.asarray(M)[a] != 0.0)


# ---------------------------------------------------------------------------
# Solo sharded closed loop + pool parity
# ---------------------------------------------------------------------------

def _solo_sharded(data, eps, key, skey, S, **over):
    kw = {"l": 4, **SPEC, **over}
    return fused_l2miss(
        data.values, jnp.asarray(data.offsets),
        jnp.ones(data.num_groups, jnp.float32), key, jnp.float32(eps),
        0.05, sample_key=skey, est_name=None,
        est_fids=jnp.asarray([estimators.moment_family_index("avg")]),
        data_shards=S, **kw)


def test_solo_sharded_closed_loop_converges(data):
    key = jax.random.PRNGKey(2)
    skey = jax.random.PRNGKey(9)
    for S in (2, 4):
        out = _solo_sharded(data, 0.2, key, skey, S)
        assert bool(out.success)
        assert np.isfinite(float(out.error))
        n = np.ravel(out.n)
        assert np.all(n >= 1) and np.all(n <= SPEC["n_cap"])


def test_sharded_lane_grows_past_one_window(data):
    """The growth clamp bounds one tick's EXTENSION, not the sample size: a
    prediction-phase lane whose n* lies several windows out keeps growing
    tick after tick until it converges."""
    n_cap = 4096
    seg_window = resolve_seg_window(n_cap, SPEC["n_max"], 4)
    out = _solo_sharded(data, 0.07, jax.random.PRNGKey(2),
                        jax.random.PRNGKey(9), 4, n_cap=n_cap, max_iters=16)
    assert bool(out.success)
    # Each group spans two of the four shards, so one tick grows it by
    # about 2 * seg_window logical slots.
    assert np.max(np.ravel(out.n)) > 4 * seg_window


def _drain(pool, specs, keys):
    from repro.aqp.query import Query
    qids = [pool.submit(Query(func=f, epsilon=e), key=keys[i])
            for i, (f, e) in enumerate(specs)]
    res = {r.qid: r for r in pool.drain()}
    return [res[qid] for qid in qids]


def _pool_specs(q):
    return [("avg", 0.25)] * (q - 1) + [("avg", 0.1)]


def test_sharded_pool_matches_solo_reference(data):
    """mesh=False pool of the 4-shard layout vs per-query fused_l2miss:
    n/iterations/success exact, theta/error at the lane-count compile
    tolerance the 1-device pool also carries."""
    from repro.serve.lane_pool import LanePool
    q, S = 6, 4
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(4), q))
    skey = jax.random.PRNGKey(9)
    pool = LanePool(data, lanes=4, data_shards=S, mesh=False,
                    sample_key=skey, seed=0, tiers=1, **SPEC)
    res = _drain(pool, _pool_specs(q), keys)
    for i, (f, e) in enumerate(_pool_specs(q)):
        solo = _solo_sharded(data, e, jnp.asarray(keys[i]), skey, S,
                             l=min(data.num_groups + 2, 12))
        r = res[i]
        assert np.array_equal(np.ravel(r.n), np.ravel(solo.n))
        assert r.iterations == int(solo.iterations)
        assert bool(r.success) == bool(solo.success)
        assert_allclose(np.ravel(r.theta), np.ravel(solo.theta), rtol=1e-5)
        assert_allclose(float(np.ravel(r.error)[0]), float(solo.error),
                        rtol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs a multi-device host mesh (set XLA_FLAGS="
                           "--xla_force_host_platform_device_count=N)")
def test_mesh_pool_bit_equal_to_solo_pool(data):
    """The tentpole contract: the shard_map pool drains BIT-equal to the
    mesh=False pool of the same layout -- the host mesh psum reduces in
    exactly the sequential fold order (exercises _splice resharding too,
    via mid-drain refills)."""
    from repro.serve.lane_pool import LanePool
    S = min(4, len(jax.devices()))
    q = 8
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(6), q))
    skey = jax.random.PRNGKey(9)
    mesh = core_mesh.make_data_mesh(S)
    kw = dict(sample_key=skey, seed=0, tiers=1, **SPEC)
    res_m = _drain(LanePool(data, lanes=2 * S, data_shards=S, mesh=mesh,
                            **kw), _pool_specs(q), keys)
    res_s = _drain(LanePool(data, lanes=2 * S, data_shards=S, mesh=False,
                            **kw), _pool_specs(q), keys)
    for a, b in zip(res_m, res_s):
        assert np.array_equal(np.ravel(a.n), np.ravel(b.n))
        assert a.iterations == b.iterations
        assert bool(a.success) == bool(b.success)
        assert (np.asarray(a.error, np.float32).tobytes()
                == np.asarray(b.error, np.float32).tobytes())
        assert (np.asarray(a.theta, np.float32).ravel().tobytes()
                == np.asarray(b.theta, np.float32).ravel().tobytes())


@pytest.mark.parametrize("S", [1, 2])
def test_step_refuses_a_2d_table(data, S):
    """Every step takes the table as ``as_columns(...)``, the sharded one
    (``data_shards > 1``) as well: the pool hands it the columns, and the
    ``(N, c)`` table raises ``TypeError``."""
    from repro.serve.lane_pool import LanePool
    pool = LanePool(data, lanes=2, tiers=1, data_shards=S, mesh=False,
                    seed=0, **SPEC)
    step, args, kw = pool._tier_program(pool._tiers[0])
    assert isinstance(args[0], tuple) and args[0] is pool.columns
    with pytest.raises(TypeError, match="as_columns"):
        step(jnp.asarray(data.values), *args[1:], **kw)
