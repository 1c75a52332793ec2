"""Phase spans and counters of the serving path (serve/tracing.py) and the
name scopes of the fused step.

  * ``AQPSession.stats()["phases"]`` counts every phase span with a
    non-negative self time, and the self times add up to the wall time of
    the calls that opened them;
  * the counters belong to the session, so they keep growing across a pool
    rebuild, and ``table_layout`` counts one call per pool built;
  * under a profiler the ``miss.*`` spans nest submit / pump > (retune,
    admit, tick > (refill, dispatch, harvest), collect), with ``sync`` under
    the phase that fetched;
  * the compiled tier and block steps carry the ``miss.gather`` /
    ``miss.estimate`` scopes in their op metadata;
  * spans add no implicit transfer, and answers do not depend on whether
    the profiler runs.
"""
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

from repro.aqp.query import Query, Request
from repro.core import sanitize
from repro.data import make_grouped
from repro.serve import AQPSession, LanePool, Planner, Route

KW = dict(B=100, n_min=300, n_max=600, max_iters=16, n_cap=1 << 13, seed=0,
          reshuffle_every=1000)
SPECS = [("avg", 0.1), ("sum", 0.05), ("var", 0.3), ("avg", 0.2)]
# Where each program span may sit (its parent; None: top level).
PARENTS = {
    "submit": {None}, "pump": {None},
    "retune": {"pump"}, "admit": {"pump"}, "tick": {"pump"},
    "collect": {"pump"}, "inline_route": {"admit"},
    "refill": {"tick"}, "dispatch": {"tick"}, "harvest": {"tick"},
    "sync": {"admit", "refill", "harvest", "inline_route"},
    "table_layout": {"admit", "retune"},
}


@pytest.fixture(scope="module")
def data():
    return make_grouped(["normal", "exp"], 60_000, seed=1, biases=[5.0, 3.0])


def _session(data, **planner):
    planner = {"pool_lanes": 2, "pool_ticks_per_sync": 1, **planner}
    return AQPSession(data, planner=Planner(**planner), **KW)


def _serve(sess, keys=None, group_by=False):
    """Submit ``SPECS`` (pinned keys if given), pump until done; returns the
    responses in order and the wall time of the submit and pump calls."""
    wall = 0.0
    tickets = []
    for i, (func, eps) in enumerate(SPECS):
        t0 = time.perf_counter()
        tickets.append(sess.submit(
            Request(query=Query(func=func, epsilon=eps, group_by=group_by)),
            key=None if keys is None else keys[i]))
        wall += time.perf_counter() - t0
    while sess.in_flight:
        t0 = time.perf_counter()
        sess.pump()
        wall += time.perf_counter() - t0
    return [sess.poll(t) for t in tickets], wall


def test_phase_counters_after_a_short_session(data):
    sess = _session(data)
    _serve(sess)
    st = sess.stats()
    ph = st["phases"]
    for name in ("submit", "pump", "retune", "admit", "tick", "refill",
                 "dispatch", "harvest", "collect", "sync"):
        assert ph[name]["calls"] >= 1, name
        assert ph[name]["s"] >= 0.0, name
    assert set(ph) <= set(PARENTS)
    assert "inline_route" not in ph           # every request rode the pool
    assert ph["submit"]["calls"] == len(SPECS)
    assert ph["sync"]["calls"] == st["syncs"]
    # One key fetch per pooled request, plus the harvest fetches.
    assert st["syncs"] >= len(SPECS) + ph["tick"]["calls"]
    assert ph["tick"]["calls"] <= ph["pump"]["calls"]
    # The table's column form is built once per pool, never per tick.
    assert ph["table_layout"]["calls"] == 1


def test_self_times_add_up_to_the_wall_time(data):
    sess = _session(data)
    _, wall = _serve(sess)
    total = sum(v["s"] for v in sess.stats()["phases"].values())
    assert total <= wall
    assert total >= 0.9 * wall


def test_counters_survive_a_pool_rebuild(data):
    sess = _session(data, cooldown=0)
    _serve(sess)
    ph0, syncs0 = sess.stats()["phases"], sess.stats()["syncs"]
    sess.planner.pool_lanes = 4               # the next idle pump rebuilds
    _serve(sess)
    ph1, syncs1 = sess.stats()["phases"], sess.stats()["syncs"]
    assert sess.pool_rebuilds == 1
    assert sess.pool.recorder is sess.recorder
    assert ph1["table_layout"]["calls"] == 1 + sess.pool_rebuilds
    # The new pool counts its own ticks only; the session counts them all.
    assert sess.pool.ticks < ph1["tick"]["calls"]
    assert syncs1 > syncs0
    for name, v in ph0.items():
        assert ph1[name]["calls"] > v["calls"], name
        assert ph1[name]["s"] >= v["s"], name


def test_pool_built_alone_keeps_its_own_recorder(data):
    pool = LanePool(data, lanes=2, **{k: KW[k] for k in (
        "B", "n_min", "n_max", "max_iters", "n_cap", "seed")})
    for func, eps in SPECS:
        pool.submit(Query(func=func, epsilon=eps))
    pool.drain()
    ph = pool.recorder.stats()
    assert ph["tick"]["calls"] == ph["harvest"]["calls"] >= 1
    assert ph["sync"]["calls"] == pool.recorder.syncs >= len(SPECS)


def test_inline_routes_run_under_their_span(data):
    # A sharded session has no grouped pool block: grouped requests run
    # on HOST, synchronously inside admission.
    sess = AQPSession(data, data_shards=2, **KW)
    rs, _ = _serve(sess, group_by=True)
    assert all(r.route is Route.HOST for r in rs)
    ph = sess.stats()["phases"]
    assert ph["inline_route"]["calls"] >= 1
    assert ph["sync"]["calls"] >= 1           # the grouped result fetch
    assert "tick" not in ph


def _program_spans(trace_dir):
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name[len("miss."):], e.start_ns, e.end_ns)
                          for e in ln.events if e.name.startswith("miss.")]
    return spans


def _parent(span, spans):
    """The innermost other span enclosing ``span`` (None at top level)."""
    name, a, b = span
    outer = [s for s in spans if s is not span and s[1] <= a and b <= s[2]
             and (s[2] - s[1]) > (b - a)]
    return min(outer, key=lambda s: s[2] - s[1])[0] if outer else None


def test_spans_nest_under_the_profiler(data, tmp_path):
    sess = _session(data)
    _serve(sess)                              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(sess)
        _serve(sess, group_by=True)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(str(tmp_path))
    seen = set()
    for sp in spans:
        parent = _parent(sp, spans)
        assert parent in PARENTS[sp[0]], (sp[0], parent)
        seen.add((parent, sp[0]))
    assert {(None, "submit"), (None, "pump"), ("pump", "retune"),
            ("pump", "admit"), ("pump", "tick"), ("pump", "collect"),
            ("tick", "refill"), ("tick", "dispatch"), ("tick", "harvest"),
            ("admit", "sync"), ("harvest", "sync")} <= seen


def test_compiled_steps_carry_the_scopes(data):
    sess = _session(data)
    _serve(sess)
    with pytest.raises(ValueError):
        sess.pool.lowered_tick(grouped=True)  # no block admitted yet
    _serve(sess, group_by=True)
    for grouped in (False, True):
        txt = sess.pool.lowered_tick(grouped=grouped).compile().as_text()
        scopes = set(re.findall(r'op_name="[^"]*?miss\.(\w+)', txt))
        assert {"gather", "estimate", "fit", "epilogue"} <= scopes, grouped


def test_spans_add_no_implicit_transfer(data, monkeypatch):
    monkeypatch.setenv("MISS_SANITIZE", "1")
    sess = _session(data)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(3), len(SPECS)))
    _serve(sess, keys=keys)
    with sanitize.no_implicit_sync():
        out, _ = _serve(sess, keys=keys)
    assert all(r is not None for r in out)


def test_answers_do_not_depend_on_the_profiler(data, tmp_path):
    sess = _session(data)
    keys = jax.random.split(jax.random.PRNGKey(7), len(SPECS))
    plain, _ = _serve(sess, keys=keys)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced, _ = _serve(sess, keys=keys)
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(plain, traced):
        assert np.array_equal(np.asarray(a.theta), np.asarray(b.theta))
        assert np.asarray(a.error).tobytes() == np.asarray(b.error).tobytes()
        assert np.array_equal(np.asarray(a.n), np.asarray(b.n))
        assert a.rows_sampled == b.rows_sampled
