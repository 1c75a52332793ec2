"""Phase-J overload-native scheduling (DESIGN.md SS7): deadline-driven
degradation, load shedding with pilot answers, and cross-tier lane
migration.

The load-bearing invariants:

  * a shed answer completes immediately (iterations == 0, no lane) and
    still satisfies its DELIVERED epsilon/delta contract: the reported
    ``delivered_epsilon`` is its measured pilot quantile, so
    ``error <= delivered_epsilon`` by construction;
  * a degraded lane IS a normal lane at the relaxed epsilon -- bit-equal
    to a solo run at the delivered bound with the same (key, sample_key);
  * a migrated lane's trajectory is bit-equal to its solo run: the move
    copies every per-lane row and the ESTIMATE bucket is compute width
    only;
  * all three policies default OFF and the phase-E pool is the exact
    special case.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.aqp.query import Query, Request
from repro.core import estimators
from repro.core.fused import bucket_ladder, fused_l2miss
from repro.data import make_grouped
from repro.serve.lane_pool import LanePool
from repro.serve.session import AQPSession
from repro.serve.slo import (AdmissionController, CostModel, eps_for_budget,
                             predict_n0)

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


def _prime(pool, *, cheap_below, coef_func="avg", coef=None, ticks=4.0,
           cheap_s=1e-5, costly_s=10.0):
    """Deterministically prime the pool's cost model: rungs <= cheap_below
    are cheap, wider rungs prohibitively slow."""
    cm = pool._slo.cost
    for w in cm.widths:
        cm._tick_s[w] = cheap_s if w <= cheap_below else costly_s
    cm._tick_s_any = cheap_s
    cm._ticks = float(ticks)
    if coef is not None:
        cm._coef[coef_func] = float(coef)


# ---------------------------------------------------------------------------
# Eq. 13 both ways
# ---------------------------------------------------------------------------

def test_eps_for_budget_inverts_predict_n0():
    """eps_for_budget is the exact inverse of the Eq.-13 allocation: feed
    the predicted total back in, recover the epsilon (modulo the safety
    margin, which only ever adds budget)."""
    beta = np.array([0.8, 0.3, 0.15], np.float32)
    for eps in (0.2, 0.05, 0.01):
        n0 = predict_n0(beta, eps, n_min=1, margin=1.0)
        got = eps_for_budget(beta, float(n0.sum()))
        # ceil() on each group only grows the budget -> eps' <= eps.
        assert got <= eps * 1.001
        assert got >= eps * 0.9

    # Monotone: shrinking the budget relaxes the bound.
    e_big = eps_for_budget(beta, 10_000.0)
    e_small = eps_for_budget(beta, 1_000.0)
    assert e_small > e_big


# ---------------------------------------------------------------------------
# Cost model + admission controller (host-side unit behavior)
# ---------------------------------------------------------------------------

def test_unprimed_model_admits():
    """No observations -> no predictions -> never degrade blind."""
    ctl = AdmissionController(bucket_ladder(1 << 13, 600), num_groups=2,
                              n_min=300)
    plan = ctl.plan(func="avg", epsilon=0.01,
                    deadline_at=time.perf_counter() + 1e-6,
                    now=time.perf_counter() - 1.0)
    assert plan.action == "admit" and plan.epsilon == 0.01


def test_controller_blown_deadline_sheds():
    ctl = AdmissionController(bucket_ladder(1 << 13, 600), num_groups=2,
                              n_min=300)
    assert ctl.plan(func="avg", epsilon=0.1, deadline_at=1.0,
                    now=2.0).action == "shed"


def test_controller_degrades_to_largest_fitting_rung():
    widths = bucket_ladder(1 << 13, 600)          # (1024, 2048, 4096, 8192)
    ctl = AdmissionController(widths, num_groups=2, n_min=300)
    cm = ctl.cost
    for w in widths:
        cm._tick_s[w] = 1e-5 if w <= 2048 else 10.0
    cm._tick_s_any = 1e-5
    cm._ticks = 4.0
    eps = 0.03
    cm._coef["avg"] = eps * math.sqrt(8192)       # predicts wm = top rung
    plan = ctl.plan(func="avg", epsilon=eps, deadline_at=0.5, now=0.0)
    assert plan.action == "degrade"
    # sqrt-law walk-down to the largest cheap rung (2048).
    assert plan.epsilon == pytest.approx(eps * math.sqrt(8192 / 2048))
    # Beyond max_degrade the controller sheds instead of lying loosely.
    tight = AdmissionController(widths, num_groups=2, n_min=300,
                                max_degrade=1.5)
    tight.cost._tick_s.update(cm._tick_s)
    tight.cost._tick_s_any = 1e-5
    tight.cost._ticks = 4.0
    tight.cost._coef["avg"] = cm._coef["avg"]
    assert tight.plan(func="avg", epsilon=eps, deadline_at=0.5,
                      now=0.0).action == "shed"


# ---------------------------------------------------------------------------
# Load shedding: pilot answers, delivered contract
# ---------------------------------------------------------------------------

def test_shed_at_submit_blown_deadline(data):
    pool = LanePool(data, lanes=2, tiers=1, degrade=True, seed=0, **SPEC)
    qid = pool.submit(Query("avg", epsilon=0.01),
                      deadline_at=time.perf_counter() - 1.0)
    # Answered before submit() returned: no queue, no lane, no tick.
    assert qid in pool.results and pool.busy_lanes == 0 \
        and pool.queue_depth == 0 and pool.ticks == 0
    r = pool.results.pop(qid)
    assert r.shed and not r.degraded and r.iterations == 0 and r.tier == -1
    assert r.epsilon == 0.01
    # The delivered contract: the reported bound is satisfied, measured.
    assert r.error <= r.delivered_epsilon
    assert r.delivered_epsilon >= r.epsilon
    # Blown deadline -> reduced replicate count, recorded.
    assert r.delivered_B == max(16, SPEC["B"] // 4)
    assert np.all(r.n == np.minimum(
        np.diff(np.asarray(data.offsets)), SPEC["n_min"]))
    assert r.theta.shape == (data.num_groups, 1)
    assert pool.stats()["shed"] == 1


def test_queued_ticket_shed_when_deadline_passes(data):
    """A ticket whose deadline expires while it queues behind busy lanes is
    swept at the next refill, pilot-answered, and never occupies a lane."""
    pool = LanePool(data, lanes=2, tiers=1, degrade=True, seed=0, **SPEC)
    # Fill both lanes with undeadlined work.
    q0 = pool.submit(Query("avg", epsilon=0.02))
    q1 = pool.submit(Query("avg", epsilon=0.02))
    pool.tick()
    assert pool.busy_lanes == 2
    # The deadline must still be ahead when submit() checks it: a submit
    # takes ~0.5-1 ms on a CPU host, more under a loaded test run.
    ddl = time.perf_counter() + 0.05
    q2 = pool.submit(Query("avg", epsilon=0.05), deadline_at=ddl)
    assert pool.queue_depth == 1      # lanes busy: it queues
    while time.perf_counter() < ddl:
        time.sleep(1e-3)
    pool.tick()
    assert q2 in pool.results
    r = pool.results.pop(q2)
    assert r.shed and r.error <= r.delivered_epsilon
    assert r.delivered_B == max(16, SPEC["B"] // 4)
    out = pool.drain()
    assert {o.qid for o in out} == {q0, q1}
    assert all(not o.shed and not o.degraded for o in out)
    assert pool.stats()["shed"] == 1


# ---------------------------------------------------------------------------
# Deadline-driven degradation
# ---------------------------------------------------------------------------

def test_degraded_lane_matches_solo_at_delivered_epsilon(data):
    """Degradation relaxes the bound at admission and nothing else: the
    lane's trajectory is bit-equal to a solo run AT the delivered epsilon
    with the same (key, sample_key)."""
    eps_req = 0.03
    skey = jax.random.PRNGKey(11)
    key = jax.random.PRNGKey(5)
    pool = LanePool(data, lanes=2, tiers=1, degrade=True, seed=0,
                    sample_key=skey, **SPEC)
    _prime(pool, cheap_below=2048,
           coef=eps_req * math.sqrt(SPEC["n_cap"]))  # predicts top rung
    qid = pool.submit(Query("avg", epsilon=eps_req), key=key,
                      deadline_at=time.perf_counter() + 0.5)
    out = pool.drain()
    r = next(o for o in out if o.qid == qid)
    assert r.degraded and not r.shed
    eps_deliv = eps_req * math.sqrt(SPEC["n_cap"] / 2048)
    assert r.epsilon == eps_req
    assert r.delivered_epsilon == pytest.approx(eps_deliv)
    assert r.delivered_epsilon > r.epsilon
    assert r.success and r.error <= r.delivered_epsilon
    assert pool.stats()["degraded"] == 1

    ref = _solo(data, "avg", key, r.delivered_epsilon, skey)
    assert np.array_equal(np.asarray(ref.n), r.n)
    assert int(ref.iterations) == r.iterations
    assert np.asarray(ref.theta).tobytes() == np.asarray(r.theta).tobytes()
    assert np.float32(ref.error).tobytes() == np.float32(r.error).tobytes()


def test_degrade_off_is_exact_special_case(data):
    """With the policies off, a deadline-carrying submission runs exactly
    as phase E did -- full fidelity, no shed/degrade counters."""
    pool = LanePool(data, lanes=2, tiers=1, seed=0, **SPEC)
    qid = pool.submit(Query("avg", epsilon=0.05),
                      deadline_at=time.perf_counter() - 1.0)  # already blown
    out = pool.drain()
    r = next(o for o in out if o.qid == qid)
    assert not r.shed and not r.degraded and r.iterations > 0
    assert r.delivered_epsilon == r.epsilon == 0.05
    s = pool.stats()
    assert s["shed"] == 0 and s["degraded"] == 0 and s["migrations"] == 0


# ---------------------------------------------------------------------------
# Cross-tier lane migration
# ---------------------------------------------------------------------------

def test_migrated_lane_bit_equal_to_solo(data):
    """A straggler that outgrows its late-spliced tier-mate's bucket is
    moved into a tier that freed up mid-flight; its answer (and its
    tier-mate's) is bit-equal to the solo run -- migration changes what
    the lane's old neighbors pay, never any answer.

    Occupied lanes march toward their targets in lockstep (growth is
    capped at n_max rows per iteration), so bucket divergence comes from
    SPLICE-TICK offsets: the burst lane retires early, the young query
    splices into the straggler's tier (the other tier is still full), and
    once the mediums retire the straggler's bucket has outgrown its young
    mate's -- it migrates into the now-free tier."""
    skey = jax.random.PRNGKey(21)
    keys = [jax.random.PRNGKey(31 + i) for i in range(5)]
    pool = LanePool(data, lanes=4, tiers=2, migrate=True, seed=0,
                    sample_key=skey, **SPEC)
    # straggler + burst -> tier 0; two mediums -> tier 1 (full); the young
    # query queues, then takes the burst's freed lane next to the straggler.
    eps = [0.03, 0.12, 0.05, 0.05, 0.05]
    qids = [pool.submit(Query("avg", epsilon=e), key=k)
            for e, k in zip(eps, keys)]
    out = {o.qid: o for o in pool.drain()}
    rs, ry = out[qids[0]], out[qids[4]]
    assert ry.tier == 0 and ry.migrations == 0
    assert pool.migrations >= 1 and rs.migrations >= 1 and rs.tier == 1
    assert pool.stats()["migrations"] == pool.migrations

    for r, e, k in ((rs, 0.03, keys[0]), (ry, 0.05, keys[4])):
        ref = _solo(data, "avg", k, e, skey)
        assert np.array_equal(np.asarray(ref.n), r.n)
        assert int(ref.iterations) == r.iterations
        assert np.asarray(ref.theta).tobytes() == np.asarray(r.theta).tobytes()
        assert np.float32(ref.error).tobytes() == \
            np.float32(r.error).tobytes()
        assert bool(ref.success) and r.success


# ---------------------------------------------------------------------------
# Session plumbing
# ---------------------------------------------------------------------------

def test_session_shed_and_contract_fields(data):
    sess = AQPSession(data, degrade=True, seed=0, **{
        k: v for k, v in SPEC.items() if k not in ("l", "ext_cap")})
    t = sess.submit(Request(Query("avg", epsilon=0.01), deadline_s=1e-9))
    guard = 0
    r = None
    while r is None and guard < 1000:
        sess.pump()
        r = sess.poll(t)
        guard += 1
    assert r is not None and r.shed
    assert r.epsilon == 0.01 and r.delivered_epsilon >= r.epsilon
    assert r.error <= r.delivered_epsilon
    assert r.slo_met is False
    assert sess.stats()["pool"]["shed"] == 1

    # An achievable deadline stays full-fidelity.
    t2 = sess.submit(Request(Query("avg", epsilon=0.05), deadline_s=60.0))
    r2 = next(o for o in sess.drain() if o.rid == t2.rid)
    assert not r2.shed and not r2.degraded and r2.success
    assert r2.delivered_epsilon == r2.epsilon == 0.05


def test_session_degraded_not_cached(data):
    """A degraded answer satisfies only the RELAXED bound, so it must not
    teach the warm cache an entry keyed on the requested epsilon."""
    sess = AQPSession(data, degrade=True, warm_cache=True, seed=0, **{
        k: v for k, v in SPEC.items() if k not in ("l", "ext_cap")})
    # Build the pool (slo_native: a deadline-carrying fusable request
    # always rides the pool), then force its cost model to degrade.
    t0 = sess.submit(Request(Query("avg", epsilon=0.03), deadline_s=60.0))
    sess.drain()
    pool = sess._pool
    assert pool is not None and pool._slo is not None
    _prime(pool, cheap_below=2048, coef_func="var",
           coef=0.03 * math.sqrt(SPEC["n_cap"]))
    t = sess.submit(Request(Query("var", epsilon=0.03), deadline_s=0.5))
    r = next(o for o in sess.drain() if o.rid == t.rid)
    assert r.degraded and r.delivered_epsilon > r.epsilon
    # The var entry was not inserted: an exact resubmit misses.
    kind, _ = sess.cache.lookup(
        sess.cache.signature(Query("var", epsilon=0.03)), epsilon=0.03)
    assert kind != "exact"
    del t0
