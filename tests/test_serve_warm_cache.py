"""Phase-H warm-start cache (DESIGN.md SS7): predicate canonicalization,
the cache signature, the WarmCache LRU, fused warm-started lanes, and the
session's WARM route.

The load-bearing invariants:

  * canonicalization is a semantics-preserving normal form -- operand
    order, int-vs-float literals, and nested conjunction shape never
    change what rows a predicate selects, and never change the signature;
  * a warm-started lane satisfies the SAME (epsilon, delta) contract as a
    cold one even when the cached prediction is wrong -- the warm jump is
    an optimization, the park/extend loop is the correctness mechanism;
  * a bit-identical repeat is replayed from the cache bit-equal, with
    ZERO pool dispatches;
  * rotating the sample epoch drops every entry (a cached answer's rows
    were drawn under the dead slot->row binding).
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.aqp.query import (Query, Request, cache_signature,
                             canonicalize_predicate, compile_predicate,
                             epsilon_bucket, predicate_signature)
from repro.core.fused import fused_l2miss, sharded_step_cache_size
from repro.data import make_grouped
from repro.serve import AQPSession, Planner, Route, WarmCache, WarmEntry
from repro.serve.warm_cache import CachedAnswer

KW = dict(B=100, n_min=300, n_max=600, max_iters=16, n_cap=1 << 13, seed=0,
          reshuffle_every=1000)


@pytest.fixture(scope="module")
def data():
    return make_grouped(["normal", "exp"], 60_000, seed=1, biases=[5.0, 3.0])


# ---------------------------------------------------------------------------
# Predicate canonicalization: property tests over a seeded AST generator
# ---------------------------------------------------------------------------

def _rand_ast(rng: random.Random, depth: int = 0):
    """A random well-formed boolean predicate AST over 3 columns."""
    def leaf():
        if rng.random() < 0.5:
            return ("col", rng.randrange(3))
        x = rng.choice([0, 1, 2, 5, -3])
        return x if rng.random() < 0.5 else ("lit", float(x))

    r = rng.random()
    if depth >= 3 or r < 0.55:
        op = rng.choice(["<", "<=", ">", ">=", "==", "!="])
        return (op, leaf(), leaf())
    if r < 0.7:
        return ("not", _rand_ast(rng, depth + 1))
    op = rng.choice(["and", "or"])
    kids = [_rand_ast(rng, depth + 1) for _ in range(rng.randrange(1, 4))]
    return (op,) + tuple(kids)


def _shuffled(rng: random.Random, ast):
    """A semantically-equal rewrite: permute symmetric/bool operands, flip
    comparison orientation, int<->float literals."""
    if not isinstance(ast, tuple):
        return float(ast) if rng.random() < 0.5 else ast
    op = ast[0]
    if op == "lit":
        x = ast[1]
        return ("lit", int(x) if float(x).is_integer() and rng.random() < 0.5
                else float(x))
    if op == "col":
        return ast
    if op == "not":
        return ("not", _shuffled(rng, ast[1]))
    if op in ("==", "!="):
        a, b = (_shuffled(rng, x) for x in ast[1:])
        return (op, b, a) if rng.random() < 0.5 else (op, a, b)
    if op in ("<", "<=", ">", ">="):
        a, b = (_shuffled(rng, x) for x in ast[1:])
        if rng.random() < 0.5:
            flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}[op]
            return (flip, b, a)
        return (op, a, b)
    kids = [_shuffled(rng, k) for k in ast[1:]]
    rng.shuffle(kids)
    return (op,) + tuple(kids)


def test_canonicalize_idempotent_and_semantics_preserving():
    rng = random.Random(7)
    vals = np.asarray(random.Random(8).choices([0, 1, 2, 5, -3], k=60),
                      np.float64).reshape(20, 3)
    for _ in range(200):
        ast = _rand_ast(rng)
        canon = canonicalize_predicate(ast)
        assert canonicalize_predicate(canon) == canon
        np.testing.assert_array_equal(
            compile_predicate(ast)(vals), compile_predicate(canon)(vals))


def test_canonicalize_rewrite_invariant():
    """Operand order, comparison orientation, and int-vs-float literals
    never change the signature (the instability the cache key must kill)."""
    rng = random.Random(9)
    for _ in range(200):
        ast = _rand_ast(rng)
        assert (canonicalize_predicate(_shuffled(rng, ast))
                == canonicalize_predicate(ast))


def test_canonicalize_examples():
    assert canonicalize_predicate((">", ("col", 0), 5)) == \
        ("<", ("lit", 5.0), ("col", 0))
    assert canonicalize_predicate(("lit", 5)) == \
        canonicalize_predicate(("lit", 5.0))
    # and-flattening + dedupe + single-child collapse
    a = ("<", ("col", 0), ("lit", 1.0))
    b = ("<", ("col", 1), ("lit", 2.0))
    assert canonicalize_predicate(("and", ("and", a, b), a)) == \
        canonicalize_predicate(("and", a, b))
    assert canonicalize_predicate(("and", a)) == a
    assert canonicalize_predicate(("not", ("not", a))) == a


@pytest.mark.parametrize("bad", [
    True, ("lit", True), ("col", 1.5), ("col", -1), ("nope", 1, 2),
    ("<", ("col", 0)), ("<", ("and",), ("col", 0)), ("not", ("col", 0)),
    ("and",), ("and", ("col", 0), ("col", 1)), (),
])
def test_canonicalize_rejects_malformed(bad):
    with pytest.raises(ValueError):
        canonicalize_predicate(bad)


def test_predicate_signature_forms():
    assert predicate_signature(None) == ()
    assert predicate_signature(lambda v: v[:, 0] > 0) is None
    assert predicate_signature((">", ("col", 0), 1)) == \
        ("<", ("lit", 1.0), ("col", 0))


# ---------------------------------------------------------------------------
# Cache signature + epsilon bucketing
# ---------------------------------------------------------------------------

def test_cache_signature_epsilon_bucketing():
    q1 = Query(func="avg", epsilon=0.100)
    q2 = Query(func="avg", epsilon=0.101)       # same geometric bucket
    q3 = Query(func="avg", epsilon=0.30)        # different bucket
    s1, s2, s3 = (cache_signature(q) for q in (q1, q2, q3))
    assert s1 == s2
    assert s1[0] == s3[0] and s1[1] != s3[1]    # same shape, other bucket
    # bucket edges are stable under float noise
    assert epsilon_bucket(0.25) == epsilon_bucket(0.25 * (1 + 1e-12))


def test_cache_signature_distinguishes_kind_epoch_and_callable():
    abs_q = Query(func="avg", epsilon=0.1)
    rel_q = Query(func="avg", epsilon_rel=0.1)
    assert cache_signature(abs_q)[0] != cache_signature(rel_q)[0]
    assert cache_signature(abs_q, dataset_epoch=1) != cache_signature(abs_q)
    assert cache_signature(
        Query(func="avg", epsilon=0.1, predicate=lambda v: v[:, 0] > 0)) \
        is None
    # equivalent predicate spellings share one signature
    pa = Query(func="count", epsilon=0.1, predicate=(">", ("col", 0), 2))
    pb = Query(func="count", epsilon=0.1,
               predicate=("<", ("lit", 2.0), ("col", 0)))
    assert cache_signature(pa) == cache_signature(pb)


# ---------------------------------------------------------------------------
# WarmCache LRU
# ---------------------------------------------------------------------------

def _entry(eps=0.1, answer=True):
    beta = np.asarray([1.0, 0.5, 0.5], np.float32)
    n = np.asarray([800, 900], np.int64)
    ans = CachedAnswer(theta=np.ones((2, 1)), error=eps / 2, success=True,
                       n=n.copy(), epsilon=eps) if answer else None
    return WarmEntry(beta=beta, n_star=n, iterations=5, epsilon=eps,
                     answer=ans)


def _sig(eps, func="avg"):
    return cache_signature(Query(func=func, epsilon=eps))


def test_warm_cache_lru_eviction_order():
    c = WarmCache(max_entries=2)
    s1, s2, s3 = _sig(0.1), _sig(0.1, "var"), _sig(0.1, "std")
    c.insert(s1, _entry())
    c.insert(s2, _entry())
    c.lookup(s1, epsilon=0.1)           # refresh s1's recency
    c.insert(s3, _entry())              # evicts s2 (LRU), not s1
    assert c.evictions == 1 and len(c) == 2
    assert c.lookup(s2, epsilon=0.1) == ("miss", None)
    assert c.lookup(s1, epsilon=0.1)[0] == "exact"
    assert c.lookup(s3, epsilon=0.1)[0] == "exact"


def test_warm_cache_byte_bound():
    e = _entry()
    c = WarmCache(max_entries=100, max_bytes=3 * e.nbytes)
    sigs = [_sig(0.1, f) for f in ("avg", "var", "std", "sum", "count")]
    for s in sigs:
        c.insert(s, _entry())
    assert c.bytes_used <= c.max_bytes and c.evictions >= 2
    assert len(c) == 3


def test_warm_cache_exact_vs_warm_vs_fallback():
    c = WarmCache()
    c.insert(_sig(0.1), _entry(eps=0.1))
    assert c.lookup(_sig(0.1), epsilon=0.1)[0] == "exact"
    # same bucket, different exact epsilon: coefficients only
    assert c.lookup(_sig(0.101), epsilon=0.101)[0] == "warm"
    # other bucket of the same shape: nearest-bucket fallback
    kind, ce = c.lookup(_sig(0.3), epsilon=0.3)
    assert kind == "warm" and ce.epsilon == 0.1
    # different shape: miss
    assert c.lookup(_sig(0.1, "var"), epsilon=0.1) == ("miss", None)
    assert (c.hits, c.exact_hits, c.warm_hits, c.misses) == (3, 1, 2, 1)


def test_warm_cache_rotate_epoch_invalidates():
    c = WarmCache()
    c.insert(c.signature(Query(func="avg", epsilon=0.1)), _entry())
    c.rotate_epoch()
    assert len(c) == 0 and c.stale == 1 and c.evictions == 0
    assert c.epoch == 1
    # the new epoch's signature is a different key by construction
    assert c.lookup(c.signature(Query(func="avg", epsilon=0.1)),
                    epsilon=0.1) == ("miss", None)


def test_predict_n0_exact_and_model():
    c = WarmCache()
    e = _entry(eps=0.1)
    # exact-epsilon repeat: the converged n_star, not the model
    np.testing.assert_array_equal(
        c.predict_n0(e, epsilon=0.1, n_min=300), [800, 900])
    # tighter bound through the Eq.-13 closed form: strictly larger sizes
    n_tight = c.predict_n0(e, epsilon=0.05, n_min=300)
    assert np.all(n_tight >= 300)
    assert n_tight.sum() > np.asarray([800, 900]).sum() or np.all(
        n_tight >= 300)
    # degenerate coefficients fall back to n_star
    bad = _entry(eps=0.1)
    bad.beta = np.asarray([500.0, 1e-12, 1e-12], np.float32)
    np.testing.assert_array_equal(
        c.predict_n0(bad, epsilon=0.05, n_min=300), [800, 900])


# ---------------------------------------------------------------------------
# Fused warm-start: wrong predictions still meet the contract
# ---------------------------------------------------------------------------

def _solo(data, eps, key, warm_n0=None, warm_beta=None):
    return fused_l2miss(
        data.values, jnp.asarray(data.offsets),
        jnp.ones(data.num_groups, jnp.float32), key, jnp.float32(eps), 0.05,
        sample_key=jax.random.PRNGKey(42), warm_n0=warm_n0,
        warm_beta=warm_beta, est_name="avg", B=KW["B"], n_min=KW["n_min"],
        n_max=KW["n_max"], l=4, max_iters=KW["max_iters"], n_cap=KW["n_cap"],
        ext_cap=KW["n_cap"])    # window >= any warm jump: one-tick confirm


def test_fused_warm_start_contract(data):
    """A warm lane converges under the same (epsilon, delta) contract as a
    cold one -- fewer iterations when the prediction is right, graceful
    extend-loop fallback when it is stale or garbage."""
    eps, key = 0.05, jax.random.PRNGKey(3)
    cold = _solo(data, eps, key)
    assert bool(cold.success) and not bool(cold.failed)
    assert int(cold.iterations) > 2     # the ramp warm-start amortizes

    # right prediction: seed with the cold run's own converged state
    warm = _solo(data, eps, key, warm_n0=np.asarray(cold.n),
                 warm_beta=np.asarray(cold.beta))
    assert bool(warm.success) and not bool(warm.failed)
    assert float(warm.error) <= eps
    assert int(warm.iterations) < int(cold.iterations)
    assert int(warm.iterations) <= 2    # one-tick confirm (+1 for rounding)

    # stale prediction (far too small) + garbage coefficients: the normal
    # extend loop takes over; the contract still holds
    stale = _solo(data, eps, key,
                  warm_n0=np.full(data.num_groups, KW["n_min"], np.int32),
                  warm_beta=np.asarray([0.0, 0.05, 0.05], np.float32))
    assert bool(stale.success) and not bool(stale.failed)
    assert float(stale.error) <= eps


def test_sharded_step_memo_is_bounded():
    from repro.core.fused import _SHARDED_STEP_CACHE_MAX, _make_sharded_step
    assert _make_sharded_step.cache_info().maxsize == _SHARDED_STEP_CACHE_MAX
    assert sharded_step_cache_size() <= _SHARDED_STEP_CACHE_MAX


# ---------------------------------------------------------------------------
# Session: exact replay, warm route, invalidation, stats
# ---------------------------------------------------------------------------

def _run_one(sess, query, rid):
    t = sess.submit(Request(query=query, rid=rid))
    while sess.in_flight:
        sess.pump()
    return sess.poll(t)


def test_session_exact_repeat_bit_equal_zero_dispatches(data):
    sess = AQPSession(data, warm_cache=True, **KW)
    q = Query(func="avg", epsilon=0.2)
    r1 = _run_one(sess, q, rid=90_001)
    d0, rows0 = sess.fused_dispatches, sess.rows_touched
    r2 = _run_one(sess, q, rid=90_002)
    assert r2.route is Route.WARM
    assert sess.fused_dispatches == d0          # zero dispatches
    assert sess.rows_touched == rows0           # zero rows sampled
    assert r2.rows_sampled == 0
    assert np.array_equal(r1.theta, r2.theta)   # bit-equal replay
    assert np.array_equal(r1.n, r2.n)
    assert r1.error == r2.error and r1.success == r2.success
    assert sess.cache_served == 1
    st = sess.stats()
    assert st["cache_hits"] == 1 and st["cache_misses"] == 1
    assert st["warm_cache"]["exact_hits"] == 1


def test_session_warm_hit_rides_pool_and_meets_contract(data):
    sess = AQPSession(data, warm_cache=True, **KW)
    _run_one(sess, Query(func="avg", epsilon=0.2), rid=90_101)
    # near-repeat: same shape, different epsilon -> warm-started pool lane
    r = _run_one(sess, Query(func="avg", epsilon=0.15), rid=90_102)
    assert r.route is Route.WARM
    assert r.success and r.error <= 0.15
    assert r.rows_sampled > 0                   # it really ran
    pool_stats = sess.stats()["pool"]
    assert pool_stats["warm_spliced"] == 1
    assert "sharded_step_cache" in pool_stats
    assert sess.stats()["warm_cache"]["warm_hits"] == 1


def test_session_pinned_key_bypasses_cache(data):
    sess = AQPSession(data, warm_cache=True, **KW)
    q = Query(func="avg", epsilon=0.2)
    key = jax.random.PRNGKey(5)
    _run_one(sess, q, rid=90_201)
    st0 = sess.cache.stats()
    t = sess.submit(Request(query=q, rid=90_202), key=key)
    while sess.in_flight:
        sess.pump()
    r = sess.poll(t)
    assert r.route is not Route.WARM            # pinned: really ran
    st1 = sess.cache.stats()
    assert st1["hits"] == st0["hits"] and st1["misses"] == st0["misses"]
    assert st1["insertions"] == st0["insertions"]   # and never re-taught


def test_session_epoch_rotation_invalidates_cache(data):
    kw = dict(KW, reshuffle_every=2)
    sess = AQPSession(data, warm_cache=True, **kw)
    q = Query(func="avg", epsilon=0.2)
    _run_one(sess, q, rid=90_301)
    _run_one(sess, Query(func="var", epsilon=0.3), rid=90_302)
    # two completions -> reshuffle + rotation: the cache must be empty
    assert sess.cache.epoch == 1 and len(sess.cache) == 0
    assert sess.cache.stats()["stale"] >= 1
    r = _run_one(sess, q, rid=90_303)           # re-runs (no replay)
    assert r.route is not Route.WARM and r.rows_sampled > 0
    # exact replays do NOT advance the epoch counter (no rows sampled)
    r2 = _run_one(sess, q, rid=90_304)
    assert r2.route is Route.WARM
    assert sess.cache.epoch == 1


def test_session_warm_lane_solo_parity_of_cold_requests(data):
    """With the cache ON, a COLD (first-seen) pooled request still answers
    bit-equal to its solo run -- the warm machinery is invisible until a
    repeat arrives."""
    sess = AQPSession(data, warm_cache=True,
                      planner=Planner(pool_lanes=2,
                                      pool_ticks_per_sync=1), **KW)
    key = jax.random.PRNGKey(11)
    t = sess.submit(Request(query=Query(func="avg", epsilon=0.2),
                            rid=90_401), key=key)
    while sess.in_flight:
        sess.pump()
    r = sess.poll(t)
    # pinned-key pool runs share the session sample_key; compare against a
    # solo run at the pool's own pilot length and epoch key
    solo = fused_l2miss(
        data.values, jnp.asarray(data.offsets),
        jnp.ones(data.num_groups, jnp.float32), key, jnp.float32(0.2), 0.05,
        sample_key=sess._sample_key, est_name="avg", B=KW["B"],
        n_min=KW["n_min"], n_max=KW["n_max"],
        l=sess._pool._spec["l"], max_iters=KW["max_iters"],
        n_cap=KW["n_cap"])
    assert np.array_equal(r.n, np.asarray(solo.n))
    assert_allclose(r.theta, np.asarray(solo.theta), rtol=1e-5)
