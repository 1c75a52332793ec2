"""Phase-D serving (DESIGN.md SS7): resumable fused steps, the heterogeneous
retire-and-refill lane pool, and the session's pool route.

The load-bearing invariants:

  * host-ticked ``fused_step`` == closed ``fused_l2miss_lanes`` while_loop
    (the step refactor is trajectory-preserving);
  * a pool-served query == a solo ``fused_l2miss`` run with the same
    (key, sample_key), even when its lane was refilled mid-flight and even
    when a straggler neighbor outlives several refills;
  * >= 3 distinct estimator funcs share ONE resident program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.aqp.query import Query
from repro.core import estimators, fused
from repro.core.fused import (as_columns, fused_l2miss, fused_l2miss_lanes,
                              fused_step, init_lane_state, lane_active,
                              lanes_result, make_lane_params)
from repro.data import make_grouped
from repro.serve.lane_pool import LanePool

# One shared spec so pool lanes and solo references compile comparably.
SPEC = dict(B=100, n_min=300, n_max=600, l=6, max_iters=16, n_cap=1 << 13,
            ext_cap=1 << 10)


@pytest.fixture(scope="module")
def data():
    return make_grouped(["normal", "exp"], 60_000, seed=1, biases=[5.0, 3.0])


def _solo(data, func, key, eps, skey, **over):
    kw = {**SPEC, "est_name": func, **over}
    return fused_l2miss(
        data.values, jnp.asarray(data.offsets),
        jnp.asarray(data.scale, jnp.float32)
        if estimators.get(func).needs_population_scale
        else jnp.ones(data.num_groups, jnp.float32),
        key, jnp.float32(eps), 0.05, sample_key=skey, **kw)


# ---------------------------------------------------------------------------
# Step refactor: host-ticked fused_step == closed while_loop
# ---------------------------------------------------------------------------

def test_step_matches_while_loop(data):
    """fused_l2miss_lanes rebuilt on fused_step must reproduce the closed
    loop bit-exactly: same body, so ticking it from the host with the same
    carry gives the same trajectory."""
    q = 3
    keys = jax.random.split(jax.random.PRNGKey(1), q)
    eps = jnp.asarray([0.15, 0.08, 0.2], jnp.float32)
    deltas = jnp.full((q,), 0.05, jnp.float32)
    skey = jax.random.PRNGKey(7)
    offsets = jnp.asarray(data.offsets)
    scale = jnp.ones((q, 2), jnp.float32)
    kw = {**SPEC, "est_name": "avg"}

    r_loop = fused_l2miss_lanes(
        data.values, offsets, scale, keys, eps, deltas, skey, **kw)

    params = make_lane_params(offsets, scale, keys, eps, deltas, skey,
                              n_cap=SPEC["n_cap"])
    state = init_lane_state(keys, 2, n_cap=SPEC["n_cap"], c_dim=1, p_dim=1,
                            n_min=SPEC["n_min"], max_iters=SPEC["max_iters"],
                            dtype=data.values.dtype)
    ticks = 0
    cols = as_columns(data.values)
    while bool(np.any(np.asarray(lane_active(state, SPEC["max_iters"])))):
        state = fused_step(cols, offsets, state, params, **kw)
        ticks += 1
    r_step = lanes_result(state)

    assert ticks == int(np.max(np.asarray(r_loop.iterations)))
    assert np.array_equal(np.asarray(r_loop.n), np.asarray(r_step.n))
    assert np.array_equal(np.asarray(r_loop.rows_sampled),
                          np.asarray(r_step.rows_sampled))
    assert np.array_equal(np.asarray(r_loop.iterations),
                          np.asarray(r_step.iterations))
    assert np.array_equal(np.asarray(r_loop.success),
                          np.asarray(r_step.success))
    assert_allclose(np.asarray(r_loop.error), np.asarray(r_step.error),
                    rtol=1e-6)
    assert_allclose(np.asarray(r_loop.theta), np.asarray(r_step.theta),
                    rtol=1e-6)


def test_multi_tick_dispatch_matches_single(data):
    """num_ticks>1 (one dispatch, fori_loop) == ticking one at a time:
    converged lanes freeze natively inside the window."""
    q = 2
    keys = jax.random.split(jax.random.PRNGKey(3), q)
    eps = jnp.asarray([0.15, 0.25], jnp.float32)
    deltas = jnp.full((q,), 0.05, jnp.float32)
    offsets = jnp.asarray(data.offsets)
    scale = jnp.ones((q, 2), jnp.float32)
    kw = {**SPEC, "est_name": "avg"}
    params = make_lane_params(offsets, scale, keys, eps, deltas,
                              jax.random.PRNGKey(9), n_cap=SPEC["n_cap"])

    def fresh():
        return init_lane_state(
            keys, 2, n_cap=SPEC["n_cap"], c_dim=1, p_dim=1,
            n_min=SPEC["n_min"], max_iters=SPEC["max_iters"],
            dtype=data.values.dtype)

    cols = as_columns(data.values)
    s1 = fresh()
    for _ in range(8):
        s1 = fused_step(cols, offsets, s1, params, **kw)
    s4 = fresh()
    for _ in range(2):
        s4 = fused_step(cols, offsets, s4, params, num_ticks=4, **kw)
    r1, r4 = lanes_result(s1), lanes_result(s4)
    assert np.array_equal(np.asarray(r1.n), np.asarray(r4.n))
    assert np.array_equal(np.asarray(r1.iterations), np.asarray(r4.iterations))
    assert_allclose(np.asarray(r1.error), np.asarray(r4.error), rtol=1e-6)


# ---------------------------------------------------------------------------
# Lane pool: retire-and-refill parity with one-shot runs
# ---------------------------------------------------------------------------

def test_pool_matches_one_shot_with_straggler_refills(data):
    """A tight-epsilon straggler occupies its lane while the neighbor lane
    retires and refills several times; every query's answer must equal the
    solo fused_l2miss run with the same (key, sample_key)."""
    skey = jax.random.PRNGKey(42)
    pool = LanePool(data, lanes=2, **SPEC, sample_key=skey, seed=5)
    specs = [("avg", 0.06)] + [("avg", 0.25)] * 4   # straggler + fast ones
    keys = jax.random.split(jax.random.PRNGKey(11), len(specs))
    qids = [pool.submit(Query(func=f, epsilon=e), key=keys[i])
            for i, (f, e) in enumerate(specs)]
    res = {r.qid: r for r in pool.drain()}
    assert len(res) == len(specs)

    # The straggler really did outlive refills: its lane held one query,
    # the other lane cycled through the remaining four.
    lane_of = {qid: res[qid].lane for qid in qids}
    straggler_lane = lane_of[qids[0]]
    neighbors = [qid for qid in qids[1:] if lane_of[qid] != straggler_lane]
    assert len(neighbors) >= 3
    assert res[qids[0]].iterations > max(res[q].iterations
                                         for q in qids[1:])

    for i, (f, e) in enumerate(specs):
        solo = _solo(data, f, keys[i], e, skey, l=pool._spec["l"])
        r = res[qids[i]]
        assert r.success and bool(solo.success)
        assert np.array_equal(r.n, np.asarray(solo.n)), (i, f, e)
        assert r.rows_sampled == int(solo.rows_sampled)
        assert r.iterations == int(solo.iterations)
        assert_allclose(r.error, float(solo.error), rtol=1e-5)
        assert_allclose(r.theta, np.asarray(solo.theta), rtol=1e-5)


def test_pool_heterogeneous_one_program(data):
    """>= 3 distinct estimator funcs share ONE resident pool program for a
    16-query mixed workload, and every answer matches the host-side exact
    reference within its bound."""
    from repro.core.l2miss import exact_answer

    skey = jax.random.PRNGKey(7)
    pool = LanePool(data, lanes=4, **SPEC, sample_key=skey, seed=3)
    scale = np.asarray(data.scale)
    workload = []
    for rep in range(4):
        workload += [
            ("avg", 0.15 + 0.02 * rep),
            ("var", 0.2 + 0.03 * rep),
            ("std", 0.12 + 0.02 * rep),
            # SUM rides at population scale: eps scales with |D|.
            ("sum", (0.15 + 0.02 * rep) * float(scale.max())),
        ]
    assert len(workload) == 16
    qids = [pool.submit(Query(func=f, epsilon=e)) for f, e in workload]

    pool.tick()                                   # compile + first tick
    cache0 = fused_step._cache_size()
    res = {r.qid: r for r in pool.drain()}        # pops early retirees too
    assert fused_step._cache_size() == cache0     # ONE resident program
    assert len(res) == 16 and pool.stats()["retired"] == 16
    assert not pool.results                       # hand-off buffer drained

    for qid, (f, e) in zip(qids, workload):
        r = res[qid]
        assert r.success, (f, e)
        assert r.error <= e
        truth = exact_answer(data, estimators.get(f)).ravel()
        dev = float(np.linalg.norm(r.theta.ravel() - truth))
        assert dev <= 2 * e, (f, e, dev)


def test_pool_admission_and_stats(data):
    pool = LanePool(data, lanes=2, **SPEC)
    # Non-moment funcs, wrong metric, relative bounds, predicates: rejected.
    with pytest.raises(ValueError):
        pool.submit(Query(func="median", epsilon=0.1))
    with pytest.raises(ValueError):
        pool.submit(Query(func="avg", epsilon=0.1, metric="linf"))
    with pytest.raises(ValueError):
        pool.submit(Query(func="avg", epsilon_rel=0.1))
    with pytest.raises(ValueError):
        pool.submit(Query(func="avg", epsilon=0.1,
                          predicate=lambda v: v[:, 0] > 0))

    for e in (0.25, 0.2, 0.3, 0.22):
        pool.submit(Query(func="avg", epsilon=e))
    assert pool.queue_depth == 4                  # backpressure visible
    assert pool.peak_queue_depth == 4
    res = pool.drain()
    st = pool.stats()
    assert st["submitted"] == st["retired"] == 4
    assert st["queue_depth"] == 0
    assert st["ticks"] >= 1 and st["dispatches"] >= 1
    assert 0.0 < st["lane_occupancy"] <= 1.0
    for r in res:
        assert r.wall_time_s >= r.queue_wait_s >= 0.0
        assert r.ticks_in_lane >= 1
    # Queued-behind queries waited: with 2 lanes and 4 queries, the last
    # two spliced strictly after ticking began.
    waited = [r for r in res if r.queue_wait_s > 0]
    assert len(waited) >= 2

    # Sample-key rotation is only legal while idle.
    pool.submit(Query(func="avg", epsilon=0.3))
    with pytest.raises(RuntimeError):
        pool.set_sample_key(jax.random.PRNGKey(1))
    pool.drain()
    pool.set_sample_key(jax.random.PRNGKey(1))    # idle: fine


def test_pool_refill_equals_fresh_pool(data):
    """The refill invariant: a query spliced into a USED lane answers
    exactly as the same query admitted into a fresh pool."""
    skey = jax.random.PRNGKey(13)
    key_a, key_b = jax.random.split(jax.random.PRNGKey(2))

    pool = LanePool(data, lanes=1, **SPEC, sample_key=skey)
    qa = pool.submit(Query(func="var", epsilon=0.2), key=key_a)
    qb = pool.submit(Query(func="std", epsilon=0.1), key=key_b)  # refill
    res = {r.qid: r for r in pool.drain()}
    assert res[qb].lane == res[qa].lane == 0      # same physical lane

    fresh = LanePool(data, lanes=1, **SPEC, sample_key=skey)
    qf = fresh.submit(Query(func="std", epsilon=0.1), key=key_b)
    rf = fresh.drain()[0]
    assert rf.qid == qf
    assert np.array_equal(res[qb].n, rf.n)
    assert res[qb].iterations == rf.iterations
    assert_allclose(res[qb].error, rf.error, rtol=1e-6)
    assert_allclose(res[qb].theta, rf.theta, rtol=1e-6)


def test_width_aware_admission(data):
    """Phase-E admission: while a wide straggler holds one tier, fresh
    queries must be placed in the narrow tier -- a fresh lane never rides
    a bucket wider than its own watermark requires when a narrower tier
    has a free lane."""
    skey = jax.random.PRNGKey(21)
    pool = LanePool(data, lanes=4, tiers=2, **SPEC, sample_key=skey, seed=9)
    assert pool.tiers == 2 and pool.tier_lanes == 2

    narrowest = pool.bucket_of(0)
    sq = pool.submit(Query(func="avg", epsilon=0.06))   # straggler
    for _ in range(6):                                  # let it grow wide
        pool.tick()
    wm = pool.tier_watermarks()
    straggler_tier = int(np.argmax(wm))
    assert wm[straggler_tier] > narrowest               # scenario is real
    assert sq not in pool.results                       # still in flight

    # Three fresh queries against two narrow free lanes: the first two must
    # be placed away from the straggler, and the third -- with every narrow
    # lane taken -- is admitted into the wide tier rather than queued
    # behind the cost model (best-effort, not hostage-taking).
    fresh = [pool.submit(Query(func="avg", epsilon=0.28)) for _ in range(3)]
    pool.tick()                                         # one refill round
    assert pool.queue_depth == 0                        # all three admitted
    res = {r.qid: r for r in pool.drain()}
    for qid in fresh[:2]:
        r = res[qid]
        assert r.tier != straggler_tier, (r.tier, wm)
        # The bucket the fresh lane rode at splice time is the one its own
        # watermark requires -- the narrowest rung, not the straggler's.
        assert pool.bucket_of(r.spliced_tier_width) == narrowest
    r3 = res[fresh[2]]
    assert r3.tier == straggler_tier
    assert r3.spliced_tier_width == wm[straggler_tier]
    assert res[sq].tier == straggler_tier
    assert res[sq].success and all(res[q].success for q in fresh)

    st = pool.stats()
    assert st["active_lane_fraction"] > 0.0
    assert st["rows_per_tick"] > 0.0
    assert st["rows_gathered"] >= sum(r.rows_sampled for r in res.values())


# ---------------------------------------------------------------------------
# Session integration: the pool route
# ---------------------------------------------------------------------------

def test_service_pool_mode_mixed_funcs(data):
    """The session's pool serves a mixed-func batch (incl. SUM at
    population scale) without per-func grouping, with answers within their
    bounds of the exact references; a lone fusable query builds the pool
    too."""
    from repro.aqp.query import Request
    from repro.serve import AQPSession, Route

    kw = dict(B=100, n_min=300, n_max=600, max_iters=16, n_cap=1 << 13,
              seed=0, reshuffle_every=1000)
    qs = [Query(func="avg", epsilon=0.2),
          Query(func="std", epsilon=0.12),
          Query(func="var", epsilon=0.25),
          Query(func="sum", epsilon=0.2 * float(np.max(data.scale))),
          Query(func="median", epsilon=0.3)]      # host-engine fallback

    sess = AQPSession(data, **kw)
    reqs = [Request(query=q) for q in qs]
    for r in reqs:
        sess.submit(r)
    by_rid = {r.rid: r for r in sess.drain()}
    rs = [by_rid[r.rid] for r in reqs]
    assert all(r.success for r in rs)
    assert [r.route for r in rs] == [Route.POOL] * 4 + [Route.HOST]
    assert sess.fused_dispatches >= 1             # pool step syncs counted
    assert sess.pool is not None
    assert sess.pool.stats()["retired"] == 4
    # ... and a singleton rides the pool as well.
    one = AQPSession(data, **kw)
    one.submit(Request(query=qs[0]))
    (r1,) = one.drain()
    assert r1.success and r1.route is Route.POOL and one.pool is not None

    # Answers agree with the exact references within their bounds.
    for q, r in zip(qs[:4], rs):
        truth = sess.engine.exact(q).ravel()
        assert np.linalg.norm(r.theta.ravel() - truth) <= 2 * q.epsilon


def test_column_table_gathers_the_row_major_rows():
    """The pool hands the step its table as 1-D columns, built once: after
    a few ticks every lane's buffer holds, bit for bit, the rows a NumPy
    gather of the row-major two-column table gives at the lane's slots --
    for the solo tier and for a grouped block alike."""
    from repro.core.sampling import GroupedData

    rng = np.random.default_rng(4)
    sizes = [30_000, 20_000]
    vals = np.stack([rng.normal(5.0, 1.0, sum(sizes)),
                     rng.exponential(3.0, sum(sizes))],
                    axis=1).astype(np.float32)
    table = GroupedData(vals, np.cumsum([0] + sizes))
    pool = LanePool(table, lanes=2, tiers=1, **SPEC, seed=3)
    assert isinstance(pool.values, tuple) and len(pool.values) == 2
    assert pool.recorder.stats()["table_layout"]["calls"] == 1
    for eps in (0.02, 0.03):
        pool.submit(Query(func="avg", epsilon=eps))
    pool.submit_group(Query(func="avg", epsilon=0.02, group_by=True))
    for _ in range(3):
        pool.tick()
    assert pool.recorder.stats()["table_layout"]["calls"] == 1

    def expected(slot_idx, filled, shape):
        want = np.zeros(shape, np.float32)
        for i, g in np.ndindex(filled.shape):
            f = filled[i, g]
            want[i, g, :f] = vals[slot_idx[i, g, :f]]
        return want

    tier = pool._tiers[0]
    blk = next(iter(pool._blocks.values()))
    for state, params in ((tier.state, tier.params), (blk.state, blk.params)):
        buf = np.asarray(state.buf)
        filled = np.asarray(state.filled)
        slot_idx = np.asarray(params.slot_idx)
        if slot_idx.ndim == 2:                       # one shared binding
            slot_idx = np.broadcast_to(slot_idx, filled.shape + (
                slot_idx.shape[-1],))
        assert np.all(filled > 0)
        assert buf.tobytes() == expected(slot_idx, filled, buf.shape).tobytes()
