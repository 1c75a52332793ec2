"""Serving grouped queries as shared-scan lane blocks.

Ports the verified end-to-end smoke into pinned tests: a grouped query
submitted to a LanePool runs as ONE block of G per-group lanes and its
answers equal ``fused_grouped`` with the pool's sample binding (exact
trajectory integers, theta rtol 1e-5, error rtol 1e-3 -- the documented
grouped tolerance, see DESIGN.md); AQPSession routes grouped traffic to
POOL, replays exact repeats from the answer cache bit-equal with zero
dispatches, and warm-starts near-repeats; sharded sessions fall back to
HOST for grouped queries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.aqp.query import Query, Request, cache_signature
from repro.core import fused
from repro.core.sampling import GroupedData
from repro.serve import AQPSession, GroupPoolResponse, LanePool, Route
from repro.serve.planner import Planner, fusable, grouped_fusable

G = 8
SPEC = dict(B=64, n_min=200, n_max=400, max_iters=16, n_cap=1 << 12)
EPS = 0.25


def _data(seed=7):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1200, 6000, size=G)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    vals = np.empty((int(offsets[-1]), 1), np.float32)
    for g in range(G):
        vals[offsets[g]:offsets[g + 1], 0] = rng.normal(
            rng.normal(5.0, 2.0), rng.uniform(0.5, 1.5), size=sizes[g])
    return GroupedData(vals, offsets)


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.fixture(scope="module")
def pool_and_responses(data):
    """One pool run shared by the parity + mixed-traffic tests."""
    pool = LanePool(data, lanes=4, seed=0, l=6, ext_cap=1 << 9, **SPEC)
    q = Query(func="avg", epsilon=EPS, delta=0.05, group_by=True)
    key = jax.random.PRNGKey(99)
    gqid = pool.submit_group(q, key=key)
    sqid = pool.submit(Query(func="avg", epsilon=0.5),
                       key=jax.random.PRNGKey(3))
    res = {r.qid: r for r in pool.drain()}
    return pool, key, res[gqid], res[sqid]


def test_pool_block_matches_fused_grouped(data, pool_and_responses):
    pool, key, gr, _ = pool_and_responses
    assert isinstance(gr, GroupPoolResponse)
    assert gr.group_by and gr.success
    offsets = np.asarray(data.offsets)
    ref = jax.tree.map(np.asarray, fused.fused_grouped(
        jnp.asarray(data.values), jnp.asarray(offsets), np.ones(G), key,
        EPS, 0.05, sample_key=pool._sample_key, est_name=None,
        est_fids=jnp.zeros((G,), jnp.int32), l=6, tau=1e-3, growth_cap=8.0,
        ext_cap=fused.resolve_ext_cap(SPEC["n_cap"], SPEC["n_max"], 1 << 9),
        metric="l2", **SPEC))
    assert np.array_equal(gr.n, ref.n)
    assert np.array_equal(gr.iterations, ref.iterations)
    assert np.array_equal(gr.group_success, ref.success)
    np.testing.assert_allclose(gr.theta, ref.theta[:, 0], rtol=1e-5)
    np.testing.assert_allclose(gr.error, ref.error, rtol=1e-3)
    assert gr.rows_sampled == int(ref.rows_sampled.sum())


def test_pool_mixes_solo_and_grouped_traffic(data, pool_and_responses):
    pool, _, gr, solo = pool_and_responses
    assert solo.success and not getattr(solo, "group_by", False)
    st = pool.stats()
    assert st["grouped_submitted"] == 1
    assert st["grouped_retired"] == 1
    assert st["busy_blocks"] == 0
    assert st["block_ticks"] > 0


def test_pool_guards_rotation_and_rekey(data):
    pool = LanePool(data, lanes=2, seed=0, l=6, ext_cap=1 << 9, **SPEC)
    q = Query(func="avg", epsilon=EPS, delta=0.05, group_by=True)
    pool.submit_group(q, key=jax.random.PRNGKey(0))
    pool.tick()
    if pool.busy_blocks:  # still resident: rebinding must be refused
        with pytest.raises(RuntimeError):
            pool.set_sample_key(jax.random.PRNGKey(1))
    pool.drain()
    pool.set_sample_key(jax.random.PRNGKey(1))  # idle pool rebinds fine
    assert pool.busy_blocks == 0


def test_planner_routes_grouped():
    p = Planner()
    q = Query(func="avg", epsilon=EPS, delta=0.05, group_by=True)
    req = Request(query=q)
    assert grouped_fusable(req)
    assert not fusable(req)  # grouped never rides solo lanes
    assert p.route(req) == Route.POOL
    assert p.route(req, warm=True) == Route.WARM
    # sharded pools have no grouped block path yet -> host fallback
    assert Planner(data_shards=2).route(req) == Route.HOST
    # non-fusable grouped shapes (unsupported metric) also go host-side
    bad = Request(query=Query(func="avg", epsilon=EPS, metric="linf",
                              group_by=True))
    assert p.route(bad) == Route.HOST


def test_grouped_cache_signature():
    q = Query(func="avg", epsilon=EPS, delta=0.05, group_by=True)
    solo = Query(func="avg", epsilon=EPS, delta=0.05)
    a = cache_signature(q, num_groups=8)
    b = cache_signature(q, num_groups=16)
    assert a != b
    assert a != cache_signature(solo)
    with pytest.raises(ValueError):
        cache_signature(q)


@pytest.fixture(scope="module")
def session_runs(data):
    """One warm session exercised three ways: cold grouped submit, exact
    repeat, near-repeat with a different epsilon."""
    sess = AQPSession(data, warm_cache=True, seed=0, **SPEC)
    q = Query(func="avg", epsilon=EPS, delta=0.05, group_by=True)
    sess.submit(Request(query=q))
    first = sess.drain()[0]
    d0 = sess.fused_dispatches
    sess.submit(Request(query=q))
    replay = sess.drain()[0]
    replay_dispatches = sess.fused_dispatches - d0
    sess.submit(Request(query=Query(func="avg", epsilon=EPS * 0.8,
                                    delta=0.05, group_by=True)))
    near = sess.drain()[0]
    return sess, first, replay, replay_dispatches, near


def test_session_routes_grouped_to_pool(session_runs):
    _, first, _, _, _ = session_runs
    assert first.route == Route.POOL
    assert first.group_by and first.success
    assert first.theta.shape == (G,)
    assert first.group_error.shape == (G,)
    assert (first.group_error <= EPS).all()
    assert first.group_success.all()


def test_session_replays_exact_repeat_bit_equal(session_runs):
    sess, first, replay, replay_dispatches, _ = session_runs
    assert replay_dispatches == 0
    assert sess.cache_served >= 1
    assert np.array_equal(first.theta, replay.theta)
    assert np.array_equal(first.group_error, replay.group_error)
    assert np.array_equal(first.group_success, replay.group_success)


def test_session_warm_starts_near_repeat(session_runs):
    _, _, _, _, near = session_runs
    assert near.route == Route.WARM
    assert near.group_by and near.success
    assert (near.group_error <= EPS * 0.8).all()


def test_sharded_session_falls_back_to_host(data):
    sess = AQPSession(data, data_shards=2, seed=0, **SPEC)
    q = Query(func="avg", epsilon=EPS, delta=0.05, group_by=True)
    sess.submit(Request(query=q))
    out = sess.drain()[0]
    assert out.route == Route.HOST
    assert out.group_by and out.success
    assert out.theta.shape == (G,)
    assert out.group_success.all()
