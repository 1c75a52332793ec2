"""Phase-F serving (DESIGN.md SS7): the asynchronous AQPSession, the Route
planner, SLO-aware admission, and the epoch-rotation deferral.

The load-bearing invariants:

  * a pool-served request == a solo ``fused_l2miss`` run with the same
    (key, sample_key) -- INCLUDING requests admitted mid-flight via
    ``submit()`` between ``pump()`` rounds;
  * a reshuffle epoch firing while pool tickets are in flight defers the
    pool's slot-table rebind to an idle point, and answers on BOTH sides
    of the rotation stay bit-equal to their solo runs;
  * fused rows are accounted at harvest: a response nobody polls (residue
    of an abandoned caller) still lands in ``rows_touched``.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.aqp.query import Query, Request
from repro.core import estimators
from repro.core.fused import fused_l2miss
from repro.data import make_grouped
from repro.serve import AQPSession, LanePool, Planner, Route
from repro.serve.planner import fusable

KW = dict(B=100, n_min=300, n_max=600, max_iters=16, n_cap=1 << 13, seed=0,
          reshuffle_every=1000)


@pytest.fixture(scope="module")
def data():
    return make_grouped(["normal", "exp"], 60_000, seed=1, biases=[5.0, 3.0])


def _solo(data, func, key, eps, skey, l):
    return fused_l2miss(
        data.values, jnp.asarray(data.offsets),
        jnp.asarray(data.scale, jnp.float32)
        if estimators.get(func).needs_population_scale
        else jnp.ones(data.num_groups, jnp.float32),
        key, jnp.float32(eps), 0.05, sample_key=skey,
        est_name=func, B=KW["B"], n_min=KW["n_min"], n_max=KW["n_max"],
        l=l, max_iters=KW["max_iters"], n_cap=KW["n_cap"])


def _assert_solo_parity(data, r, key, func, eps, skey, l):
    solo = _solo(data, func, key, eps, skey, l)
    assert np.array_equal(r.n, np.asarray(solo.n)), (func, eps)
    assert r.rows_sampled == int(solo.rows_sampled)
    assert_allclose(r.error, float(solo.error), rtol=1e-5)
    assert_allclose(r.theta, np.asarray(solo.theta), rtol=1e-5)


def _pump_done(sess, tickets):
    """Pump until every ticket finished; poll them in order."""
    while sess.in_flight:
        sess.pump()
    return [sess.poll(t) for t in tickets]


# ---------------------------------------------------------------------------
# Session lifecycle + mid-flight admission parity (the tentpole contract)
# ---------------------------------------------------------------------------

def test_session_mid_flight_admission_solo_parity(data):
    """Requests admitted via submit() between pump() rounds -- while a
    straggler holds its lane -- answer bit-equal to solo runs."""
    sess = AQPSession(data, planner=Planner(pool_lanes=2,
                                            pool_ticks_per_sync=1), **KW)
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    specs = [("avg", 0.06), ("avg", 0.3), ("var", 0.3), ("std", 0.25)]

    t0 = sess.submit(Request(query=Query(func="avg", epsilon=0.06)),
                     key=keys[0])
    sess.pump()                         # straggler admitted + ticking
    assert sess.poll(t0) is None        # non-blocking: still in flight
    tickets = [t0]
    for (f, e), k in zip(specs[1:], keys[1:]):
        # Mid-flight: the pool is busy; no drain between submissions.
        assert sess._pool.busy_lanes > 0
        tickets.append(sess.submit(Request(query=Query(func=f, epsilon=e)),
                                   key=k))
        sess.pump()
    rs = _pump_done(sess, tickets)

    l = sess._pool._spec["l"]
    skey = sess._sample_key
    for r, (f, e), k in zip(rs, specs, keys):
        assert r.route is Route.POOL and r.success
        _assert_solo_parity(data, r, k, f, e, skey, l)
    # Collected tickets are gone (bounded memory), unknown rids raise.
    with pytest.raises(KeyError):
        sess.poll(tickets[0])
    with pytest.raises(KeyError):
        sess.poll(10**9)


def test_session_submit_validation(data):
    sess = AQPSession(data, **KW)
    with pytest.raises(TypeError):
        sess.submit(Query(func="avg", epsilon=0.2))     # must wrap in Request
    req = Request(query=Query(func="avg", epsilon=0.2))
    sess.submit(req)
    with pytest.raises(ValueError):
        sess.submit(req)                                # rid already live
    sess.drain()
    with pytest.raises(ValueError):
        Request(query=Query(func="avg", epsilon=0.2), deadline_s=0.0)
    r1 = Request(query=Query(func="avg", epsilon=0.2))
    r2 = Request(query=Query(func="avg", epsilon=0.2))
    assert r1.rid != r2.rid                             # stable unique ids


def test_session_slo_fields(data):
    """deadline_s is judged against real submit->completion latency."""
    sess = AQPSession(data, **KW)
    t_ok = sess.submit(Request(query=Query(func="avg", epsilon=0.3),
                               deadline_s=300.0))
    t_none = sess.submit(Request(query=Query(func="var", epsilon=0.3)))
    r_ok, r_none = _pump_done(sess, [t_ok, t_none])
    assert r_ok.slo_met is True and r_ok.deadline_s == 300.0
    assert r_none.slo_met is None and r_none.deadline_s is None
    assert r_ok.latency_s > 0.0
    # An impossible budget is reported missed, never enforced by kill.
    t_miss = sess.submit(Request(query=Query(func="avg", epsilon=0.25),
                                 deadline_s=1e-9))
    (r_miss,) = _pump_done(sess, [t_miss])
    assert r_miss.success and r_miss.slo_met is False


# ---------------------------------------------------------------------------
# Epoch rotation with a non-empty pool (deferred set_sample_key)
# ---------------------------------------------------------------------------

def test_rotation_defers_while_in_flight_and_answers_stay_solo_exact(data):
    """``reshuffle_every`` firing while tickets are in flight must defer
    the pool rebind to an idle point: the in-flight straggler finishes
    under the OLD binding (bit-equal to its solo run), and the first
    request after the idle rotation runs under the NEW one."""
    kw = {**KW, "reshuffle_every": 2}
    sess = AQPSession(data, planner=Planner(pool_lanes=2,
                                            pool_ticks_per_sync=1), **kw)
    keys = jax.random.split(jax.random.PRNGKey(23), 4)

    skey_old = np.asarray(sess._sample_key).copy()
    t_strag = sess.submit(
        Request(query=Query(func="avg", epsilon=0.06)), key=keys[0])
    sess.pump()
    # Two fast completions cross the epoch threshold while the straggler
    # is mid-flight.
    t_f1 = sess.submit(Request(query=Query(func="avg", epsilon=0.3)),
                       key=keys[1])
    t_f2 = sess.submit(Request(query=Query(func="var", epsilon=0.3)),
                       key=keys[2])
    pool = sess._pool
    epochs0 = pool.sample_epochs
    while t_f1.rid in sess._inflight or t_f2.rid in sess._inflight:
        sess.pump()

    # Both fast queries are done, so the epoch rotated -- while the
    # straggler still holds its lane: the pool rebind must be PARKED.
    assert t_strag.rid in sess._inflight
    skey_new = np.asarray(sess._sample_key)
    assert not np.array_equal(skey_new, skey_old)
    assert pool.stats()["pending_rotation"]
    assert pool.sample_epochs == epochs0
    assert np.array_equal(np.asarray(pool._sample_key), skey_old)

    (r_s,) = _pump_done(sess, [t_strag])
    l = pool._spec["l"]
    # Every query of this stream ran under the OLD binding.
    assert r_s.success
    _assert_solo_parity(data, r_s, keys[0], "avg", 0.06, skey_old, l)
    for t, k, f, e in ((t_f1, keys[1], "avg", 0.3),
                       (t_f2, keys[2], "var", 0.3)):
        _assert_solo_parity(data, sess.poll(t), k, f, e, skey_old, l)

    # The parked rotation lands at the next idle tick, BEFORE the next
    # request splices: it reproduces the solo run under the NEW key.
    # (That request's own completion crosses the epoch threshold again --
    # the pool is idle by then, so the second rotation applies at once.)
    t_next = sess.submit(Request(query=Query(func="std", epsilon=0.25)),
                         key=keys[3])
    (r_n,) = _pump_done(sess, [t_next])
    assert pool.sample_epochs == epochs0 + 2
    assert not pool.stats()["pending_rotation"]
    _assert_solo_parity(data, r_n, keys[3], "std", 0.25, skey_new, l)


def test_pool_request_sample_key_applies_when_idle(data):
    """The pool-level deferral contract: request_sample_key applies
    immediately on an idle pool, parks while lanes are busy, and the
    strict set_sample_key still refuses in-flight rotation."""
    pool = LanePool(data, lanes=2, B=100, n_min=300, n_max=600, max_iters=16,
                    n_cap=1 << 13)
    k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    assert pool.request_sample_key(k1) is True          # idle: applied now
    assert pool.sample_epochs == 1

    pool.submit(Query(func="avg", epsilon=0.05))
    pool.tick()
    with pytest.raises(RuntimeError):
        pool.set_sample_key(k2)                         # strict path refuses
    assert pool.request_sample_key(k2) is False         # parked
    assert pool.stats()["pending_rotation"]
    assert np.array_equal(np.asarray(pool._sample_key), np.asarray(k1))
    pool.drain()
    pool.tick()                                         # idle tick applies it
    assert pool.sample_epochs == 2
    assert not pool.stats()["pending_rotation"]
    assert np.array_equal(np.asarray(pool._sample_key), np.asarray(k2))


# ---------------------------------------------------------------------------
# Planner: routing + continuous re-tuning
# ---------------------------------------------------------------------------

def test_planner_routes(data):
    """Routing: HOST for non-fusable, POOL for every fusable request -- a
    cold singleton builds the pool, a wave and a busy join ride it."""
    sess = AQPSession(data, **KW)
    assert not fusable(Request(query=Query(func="median", epsilon=0.3)))
    assert not fusable(Request(query=Query(func="avg", epsilon_rel=0.1)))
    assert not fusable(Request(query=Query(func="avg", epsilon=0.1,
                                           metric="linf")))

    t_host = sess.submit(Request(query=Query(func="median", epsilon=0.3)))
    t_solo = sess.submit(Request(query=Query(func="avg", epsilon=0.3)))
    r_host, r_solo = _pump_done(sess, [t_host, t_solo])
    assert r_host.route is Route.HOST
    assert r_solo.route is Route.POOL
    assert sess._pool is not None       # the singleton built the pool

    wave = [sess.submit(Request(query=Query(func="avg", epsilon=0.05 + e)))
            for e in (0.0, 0.2)]
    sess.pump()                         # wave of 2 -> the pool is busy
    assert sess._pool.busy_lanes > 0
    t_join = sess.submit(Request(query=Query(func="var", epsilon=0.3)))
    rs = _pump_done(sess, wave + [t_join])
    assert all(r.route is Route.POOL for r in rs)   # incl. the busy join


def test_sharded_session_serves_a_cold_singleton_in_the_pool(data):
    """A lone fusable query on a two-shard session runs in the two-shard
    pool (``mesh=False`` keeps the shard layout on one device) and meets
    its bound against the exact answer."""
    sess = AQPSession(data, data_shards=2, mesh=False, **KW)
    q = Query(func="avg", epsilon=0.2)
    (r,) = _pump_done(sess, [sess.submit(Request(query=q))])
    assert r.route is Route.POOL and r.success
    st = sess.stats()["pool"]
    assert st["data_shards"] == 2 and st["retired"] == 1
    truth = sess.engine.exact(q).ravel()
    assert np.linalg.norm(r.theta.ravel() - truth) <= q.epsilon


def test_planner_retunes_cadence_and_rebuilds_at_idle(data):
    """The sliding-window policy: ticks_per_sync follows the epsilon
    spread of the live stream, and a lane-count drift triggers an
    idle-point rebuild after the cooldown."""
    planner = Planner(window=6, cooldown=4)
    sess = AQPSession(data, planner=planner, **KW)

    # Wave of 6 uniform-epsilon requests: lanes = (6+1)//2 -> 3 -> even 4;
    # spread 1.0 <= 1.5 -> 2 ticks per dispatch.
    for _ in range(6):
        sess.submit(Request(query=Query(func="avg", epsilon=0.3)))
    sess.drain()
    assert sess._pool.lanes == 4
    assert sess._pool.ticks_per_sync == 2

    # Straggler-prone traffic (wide spread) retunes the cadence to 1 on
    # the LIVE pool -- no rebuild needed.
    for eps in (0.05, 0.3, 0.05, 0.3):
        sess.submit(Request(query=Query(func="avg", epsilon=eps)))
    sess.drain()
    assert sess._pool.ticks_per_sync == 1
    assert planner.retunes >= 1
    assert sess.pool_rebuilds == 0

    # Singleton traffic shrinks the backlog window; once the cooldown
    # passes, the pool is rebuilt (at an idle pump) at the smaller size.
    for _ in range(8):
        sess.submit(Request(query=Query(func="avg", epsilon=0.3)))
        sess.drain()
    assert sess.pool_rebuilds >= 1
    assert sess._pool.lanes == 2


# ---------------------------------------------------------------------------
# SLO-aware admission ordering
# ---------------------------------------------------------------------------

def test_priority_and_deadline_admission_order(data):
    """While one lane is held by a straggler, queued tickets splice by
    (priority desc, deadline asc, FIFO) -- and ordering changes only WHEN
    a query runs, never its answer."""
    pool = LanePool(data, lanes=1, tiers=1, B=100, n_min=300, n_max=600,
                    max_iters=16, n_cap=1 << 13, seed=3)
    pool.submit(Query(func="avg", epsilon=0.06))        # occupies the lane
    pool.tick()
    now = time.perf_counter()
    q_fifo = pool.submit(Query(func="avg", epsilon=0.3))
    q_ddl = pool.submit(Query(func="avg", epsilon=0.3),
                        deadline_at=now + 0.5)
    q_pri = pool.submit(Query(func="avg", epsilon=0.3), priority=5)
    res = {r.qid: r for r in pool.drain()}
    # priority class first, then earliest deadline, then FIFO.
    assert res[q_pri].queue_wait_s < res[q_ddl].queue_wait_s
    assert res[q_ddl].queue_wait_s < res[q_fifo].queue_wait_s
    assert all(r.success for r in res.values())


def test_session_priority_reaches_pool(data):
    sess = AQPSession(data, planner=Planner(pool_lanes=1), **KW)
    sess.submit(Request(query=Query(func="avg", epsilon=0.06)))
    sess.pump()                                         # the lane is busy
    t_lo = sess.submit(Request(query=Query(func="avg", epsilon=0.3)))
    t_hi = sess.submit(Request(query=Query(func="avg", epsilon=0.3),
                               priority=3, deadline_s=60.0))
    r_lo, r_hi = _pump_done(sess, [t_lo, t_hi])
    assert r_hi.queue_wait_s < r_lo.queue_wait_s
    assert r_hi.slo_met is True
    sess.drain()                                        # collect straggler


# ---------------------------------------------------------------------------
# Accounting: harvest-time rows (the residue fix)
# ---------------------------------------------------------------------------

def test_residue_rows_still_accounted(data):
    """A pool response nobody polls (its ticket belongs to an abandoned
    caller) still lands in rows_touched -- rows are accounted at harvest,
    not at collection."""
    sess = AQPSession(data, **KW)
    stray = Request(query=Query(func="avg", epsilon=0.3))
    sess.submit(stray)                  # abandoned: never polled
    t = sess.submit(Request(query=Query(func="var", epsilon=0.3)))
    while sess.in_flight:
        sess.pump()
    assert sess.poll(t).success
    pool = sess.pool
    assert pool.stats()["retired"] == 2
    # Every gathered row -- stray included -- is in the fused accounting.
    assert sess._fused_rows == pool.stats()["rows_gathered"]
    assert sess.rows_touched == sess.store.rows_touched + sess._fused_rows
    assert [r.rid for r in sess.drain()] == [stray.rid]
    with pytest.raises(KeyError):
        sess.poll(stray.rid)            # popped by drain


# ---------------------------------------------------------------------------
# Steady-state recompile sentinel (misslint/sanitize harness, phase K)
# ---------------------------------------------------------------------------

def test_steady_state_serving_never_recompiles(data, monkeypatch):
    """After warmup, a submit/pump/poll loop over repeated request shapes
    compiles NOTHING: the fused_step cache is frozen, the pool's
    steady_recompiles counter stays 0, and the full sanitizer (transfer
    guard + PRNG-root lock + compile sentinel) holds over the loop."""
    from repro.core import sanitize
    from repro.core.fused import fused_step

    monkeypatch.setenv("MISS_SANITIZE", "1")
    sess = AQPSession(data, planner=Planner(pool_lanes=2,
                                            pool_ticks_per_sync=1), **KW)
    # Warmup: drive one request per estimator family end to end, so every
    # program a steady stream needs (admission-wave splits, both tier
    # widths, both finishers) is resident.
    wkeys = jax.random.split(jax.random.PRNGKey(7), 2)
    _pump_done(sess, [
        sess.submit(Request(query=Query(func=f, epsilon=0.3)), key=k)
        for f, k in zip(("avg", "var"), wkeys)])

    cache0 = fused_step._cache_size()
    keys = jax.random.split(jax.random.PRNGKey(23), 12)
    with sanitize.steady_state(fused_step):
        tickets = []
        for i, k in enumerate(keys):
            f = ("avg", "var")[i % 2]
            tickets.append(sess.submit(
                Request(query=Query(func=f, epsilon=0.25)), key=k))
            sess.pump()                 # interleave admission with ticking
        rs = _pump_done(sess, tickets)

    assert all(r.route is Route.POOL for r in rs)
    assert fused_step._cache_size() == cache0
    assert sess._pool.stats()["steady_recompiles"] == 0
    # The answers are still the real thing, not a warm-cache short-circuit.
    l = sess._pool._spec["l"]
    _assert_solo_parity(data, rs[0], keys[0], "avg", 0.25,
                        sess._sample_key, l)
