"""End-to-end AQP serving: a TPC-H query suite through the async session.

    PYTHONPATH=src python examples/aqp_tpch.py [--rows 1000000]

Builds a synthetic lineitem table and serves Listing-1 queries through the
asynchronous :class:`AQPSession` (DESIGN.md SS7 phase F): each request
carries an ERROR clause (epsilon, delta) AND an SLO envelope (deadline,
priority), is submitted into the live arrival queue, and is collected with
a non-blocking submit/poll/pump loop -- answers stream back as lanes
retire, tight-epsilon stragglers keep ticking while loose queries overtake
them through freed lanes.  Host-only queries (predicates, Linf, ordering)
ride the same session and route to the host engine.  The final batch is
submitted whole and collected with ``drain()``, the synchronous shape of
the same session machinery.
"""
import argparse
import time

import numpy as np

from repro.aqp import AQPEngine, Query, Request
from repro.core.extensions import metric_value
from repro.data.tpch import add_group_bias, make_lineitem
from repro.serve import AQPSession


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    args = ap.parse_args()

    data, _ = make_lineitem(rows=args.rows, group_by="returnflag", seed=2)
    data = add_group_bias(data, 0.05)
    sess = AQPSession(data, B=300, n_min=1000, n_max=2000, seed=0)
    eng: AQPEngine = sess.engine
    print(f"lineitem: {args.rows:,} rows, {data.num_groups} RETURNFLAG groups")

    # Absolute L2 bounds sized off the exact answer's magnitude (an example
    # convenience; production would use epsilon_rel through the host path).
    avg_mag = float(np.linalg.norm(eng.exact(Query(func="avg", epsilon=1.0))))
    sum_mag = float(np.linalg.norm(eng.exact(Query(func="sum", epsilon=1.0))))

    suite = [
        ("AVG +-1% (tight straggler)",
         Request(query=Query(func="avg", epsilon=0.01 * avg_mag),
                 deadline_s=120.0, priority=1)),
        ("AVG +-2%",
         Request(query=Query(func="avg", epsilon=0.02 * avg_mag),
                 deadline_s=60.0)),
        ("VAR +-5% of AVG-scale",
         Request(query=Query(func="var", epsilon=0.05 * avg_mag**2),
                 deadline_s=60.0)),
        ("SUM +-2%",
         Request(query=Query(func="sum", epsilon=0.02 * sum_mag),
                 deadline_s=60.0)),
        ("COUNT(price>30k) +-2% (host)",
         Request(query=Query(func="count", epsilon_rel=0.02,
                             predicate=lambda v: v[:, 0] > 30_000.0))),
        ("AVG ordered Top-k (host)",
         Request(query=Query(func="avg", metric="order"))),
    ]

    # --- async submit / poll loop: answers stream back as lanes retire ---
    pending = {}
    for name, req in suite:
        ticket = sess.submit(req)
        pending[ticket.rid] = (name, ticket, req)
        print(f"submitted [{name}] rid={ticket.rid}")
    while pending:
        sess.pump()                      # one non-blocking scheduler round
        for rid in list(pending):
            name, ticket, req = pending[rid]
            r = sess.poll(ticket)        # None while still in flight
            if r is None:
                continue
            del pending[rid]
            q = req.query
            truth = eng.exact(q)
            d = metric_value("l2" if q.metric == "order" else q.metric,
                             r.theta.ravel(), truth.ravel())
            slo = ("no deadline" if r.slo_met is None
                   else f"SLO {'met' if r.slo_met else 'MISSED'}")
            print(f"\n[{name}] via {r.route.value}: "
                  f"{'ok' if r.success else 'failed'} "
                  f"in {r.latency_s:.2f}s ({slo}, "
                  f"queue {r.queue_wait_s * 1e3:.0f}ms)")
            print(f"  answer {np.round(r.theta.ravel(), 2)}")
            print(f"  exact  {np.round(truth.ravel(), 2)}")
            if q.metric == "order":
                ok = metric_value("order", r.theta.ravel(),
                                  truth.ravel()) == 0
                print(f"  ordering preserved: {ok}")
            else:
                print(f"  {q.metric} error {d:.4g}")
        time.sleep(0.001)                # a real client would do other work

    st = sess.stats()
    print(f"\nsession: {st['completed']} served, "
          f"{st['fused_dispatches']} fused dispatches, "
          f"{st['rows_touched']:,} rows touched")

    # --- overload-native serving (DESIGN.md SS7 phase J) ------------------
    # With degrade=True the deadline becomes load-bearing: admission
    # relaxes epsilon to the largest bucket rung whose predicted cost fits
    # the remaining budget (a DEGRADED answer, relaxed bound reported in
    # delivered_epsilon), and a deadline that cannot be met even degraded
    # is SHED -- an immediate partial answer from a small pilot sample,
    # its measured error bar reported instead of queueing into a miss.
    print("\n--- overload-native: degraded + shed answers (phase J) ---")
    sess2 = AQPSession(data, B=300, n_min=1000, n_max=2000, seed=3,
                       degrade=True)
    tight = Query(func="avg", epsilon=0.005 * avg_mag)
    # Prime the admission cost model: a few full-fidelity runs teach it
    # the per-rung tick cost and the epsilon-vs-n sqrt law (an unprimed
    # model admits everything untouched -- degradation is never blind).
    for _ in range(3):
        sess2.submit(Request(query=tight, deadline_s=300.0))
    t0 = time.perf_counter()
    sess2.drain()
    full_s = (time.perf_counter() - t0) / 3
    # One throwaway shed compiles the pilot program (one per estimator
    # func); the showcased shed below is then a single warm dispatch.
    sess2.submit(Request(query=tight, deadline_s=1e-6))
    sess2.drain()

    def show(label, r, eps_req):
        kind = "shed" if r.shed else ("degraded" if r.degraded else "full")
        print(f"[{label}] {kind}: requested eps {eps_req:.4g} -> "
              f"delivered eps {r.delivered_epsilon:.4g} "
              f"(B={r.delivered_B}), n={np.round(np.mean(r.n)):.0f} "
              f"rows/group, {r.latency_s * 1e3:.1f}ms, "
              f"SLO {'met' if r.slo_met else 'MISSED'}")

    # Budget ~40% of the measured full-fidelity latency: enough for a
    # coarser rung, not for the requested epsilon.
    t_deg = sess2.submit(Request(query=tight, deadline_s=0.4 * full_s))
    r_deg = next(o for o in sess2.drain() if o.rid == t_deg.rid)
    show("tight deadline", r_deg, tight.epsilon)
    # A ~10ms budget is hopeless at any rung: shed at submit, answered
    # from the pilot before this call returns.
    t_shed = sess2.submit(Request(query=tight, deadline_s=0.010))
    r_shed = next(o for o in sess2.drain() if o.rid == t_shed.rid)
    show("blown deadline", r_shed, tight.epsilon)
    pst = sess2.stats()["pool"]
    print(f"pool counters: shed={pst['shed']} degraded={pst['degraded']} "
          f"migrations={pst['migrations']}")

    # --- a whole batch: submit everything, then drain ---
    sess3 = AQPSession(data, B=300, n_min=1000, n_max=2000, seed=1)
    batch = [Query(func="avg", epsilon=0.02 * avg_mag),
             Query(func="var", epsilon=0.05 * avg_mag**2),
             Query(func="sum", epsilon=0.02 * sum_mag)]
    t0 = time.perf_counter()
    for q in batch:
        sess3.submit(Request(query=q))
    rs = sess3.drain()
    print(f"\nsubmit + drain: {len(rs)} queries in "
          f"{time.perf_counter() - t0:.2f}s, all "
          f"{'ok' if all(r.success for r in rs) else 'FAILED'}; "
          f"routes {sorted({r.route.value for r in rs})}")


if __name__ == "__main__":
    main()
