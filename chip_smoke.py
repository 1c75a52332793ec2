"""Chip smoke test: serve TPC-H lineitem through ``AQPSession`` on a TPU.

    python chip_smoke.py [--seed 0]              # SF 10 on one chip
    python chip_smoke.py --chips 4 [--seed 0]    # SF 40 row-sharded over four

One chip: generates lineitem at scale factor 10 (about 60M rows, grouped by
RETURNFLAG) from ``--seed`` and serves, through a session with default
options (``use_kernel="auto"``):

* AVG, SUM, COUNT and VAR with absolute L2 bounds (POOL route, the
  ``poisson_bootstrap`` kernel inside the tick);
* one GROUP BY AVG (POOL route, a grouped lane block, ``segment_boot_call``);
* one COUNT under a structured predicate (HOST route, L2Miss with the
  kernel).

Every answer is checked against a float64 NumPy full scan of the table.
The ESTIMATE kernels are then called compiled (``interpret=False``) at pool
width, and their replicate moment sums -- and those of the jnp ESTIMATE --
are compared with a float64 reference to ``MOMENT_RTOL``.

Four chips (``--chips 4``, this phase only): generates lineitem at SF 40 and
drains an AVG/SUM/COUNT mix through ``AQPSession(data_shards=4)`` (the
row-sharded pool on a 4-device ``("data",)`` mesh) and through the same
session with ``mesh=False`` (one device, same shard layout), then compares
the two drains with each other and with the full scan.

Exits non-zero, with no result line, when JAX finds no TPU or any check
fails.  The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
DELTA = 0.01          # per-request error probability; 6 answers => <= 6%
MOMENT_RTOL = 1e-5    # kernel / jnp moment sums vs float64, see check_moments
POOL_LANES, POOL_WIDTH, POOL_B = 8, 1 << 16, 300   # check_moments shapes
PRED_MIN_PRICE = 30_000.0


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def device_summary() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- float64 reference ------------------------------------------------------

def exact_answers(vals: np.ndarray, offsets: np.ndarray) -> dict:
    """Per-group float64 full-scan answers.

    SUM and COUNT report ``|D_g|`` times the mean of their measure column
    (paper SS2.2.1): with a predicate that column is the 0/1 indicator, so
    COUNT counts matching rows; without one COUNT equals SUM.
    """
    out = {k: [] for k in ("avg", "var", "sum", "count", "count_pred")}
    for g in range(len(offsets) - 1):
        x = vals[offsets[g]:offsets[g + 1]].astype(np.float64)
        out["avg"].append(x.mean())
        out["var"].append(x.var())
        out["sum"].append(x.sum())
        out["count"].append(x.sum())
        out["count_pred"].append(float(np.count_nonzero(x > PRED_MIN_PRICE)))
    return {k: np.asarray(v) for k, v in out.items()}


def np_poisson1_weights(seed: np.ndarray, row: np.ndarray,
                        col: np.ndarray) -> np.ndarray:
    """NumPy twin of ``prng.poisson1_weights_at`` (uint32 wrap-around)."""
    from repro.kernels.prng import POISSON1_CDF

    u32 = np.uint32
    with np.errstate(over="ignore"):
        h = (row.astype(u32) * u32(0x9E3779B1)
             ^ col.astype(u32) * u32(0x85EBCA77)
             ^ seed.astype(u32) * u32(0xC2B2AE3D))
        h = h ^ (h >> u32(16))
        h = h * u32(0x7FEB352D)
        h = h ^ (h >> u32(15))
        h = h * u32(0x846CA68B)
        h = h ^ (h >> u32(16))
    u = (h >> u32(8)).astype(np.float32) * np.float32(2.0 ** -24)
    w = np.zeros(u.shape, np.float64)
    for c in POISSON1_CDF:
        w += u >= np.float32(c)
    return w


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Max relative error per moment (last axis) over all other axes."""
    got = np.asarray(got, np.float64)
    err = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)
    return err.reshape(-1, ref.shape[-1]).max(axis=0)


# -- one chip ----------------------------------------------------------------

def check_answer(name, resp, exact, eps, want_route, failures) -> dict:
    theta = np.asarray(resp.theta, np.float64).ravel()
    if resp.group_by:
        dist = np.abs(theta - exact)          # per-group contract
        within = bool((dist <= eps).all())
        dist_s = np.array2string(dist, precision=4)
    else:
        dist = float(np.linalg.norm(theta - exact))
        within = dist <= eps
        dist_s = f"{dist:.6g}"
    ok = within and bool(resp.success) and resp.route == want_route
    log(f"  {name:<28} route={resp.route.value:<5} success={resp.success} "
        f"n={np.asarray(resp.n).ravel().tolist()}")
    log(f"    answer {np.array2string(theta, precision=10)}")
    log(f"    exact  {np.array2string(exact, precision=10)}")
    log(f"    l2 distance {dist_s}  epsilon {eps:.6g}  "
        f"error bar {resp.error:.6g}  -> {'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(f"{name}: route={resp.route.value} (want "
                        f"{want_route.value}) success={resp.success} "
                        f"distance={dist_s} epsilon={eps}")
    return {"route": resp.route.value, "success": bool(resp.success),
            "distance": dist.tolist() if resp.group_by else dist,
            "epsilon": eps, "ok": ok}


def check_moments(vals, offsets, *, seed, failures) -> dict:
    """Compiled ESTIMATE kernels vs the jnp ESTIMATE vs float64.

    Replicate sums ``[sum w, sum w x, sum w x^2]`` at pool width
    (``POOL_LANES`` lanes x 3 groups x ``POOL_WIDTH`` slots, ``POOL_B``
    replicates; a ``POOL_WIDTH``-element grouped stream).  All terms are
    non-negative, so f32 accumulation keeps the relative error near 1e-6;
    an f32 contraction that rounds its operands to bf16 shows up as
    ~1e-4 at a lane's n_min rows.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import bootstrap
    from repro.kernels.poisson_bootstrap import ops as pb_ops
    from repro.kernels.segment_agg import ops as seg_ops

    rng = np.random.default_rng(seed + 1)
    q, m, w, B = POOL_LANES, len(offsets) - 1, POOL_WIDTH, POOL_B
    sizes = np.diff(offsets)
    starts = np.stack([offsets[g] + rng.integers(0, sizes[g] - w, q)
                       for g in range(m)], axis=1)            # (q, m)
    x = vals[starts[..., None] + np.arange(w)]                # (q, m, w)
    lens = rng.integers(1000, w + 1, (q, m))
    mask = (np.arange(w) < lens[..., None]).astype(np.float32)
    seeds = rng.integers(0, 2**32, (q, m), dtype=np.uint64).astype(np.uint32)

    cols = np.arange(B, dtype=np.uint32)
    ref = np.zeros((q, m, B, 3))
    for i in range(q):
        for g in range(m):
            L = int(lens[i, g])
            W = np_poisson1_weights(seeds[i, g], np.arange(L)[:, None],
                                    cols[None, :])            # (L, B)
            xv = x[i, g, :L].astype(np.float64)
            ref[i, g] = W.T @ np.stack([np.ones(L), xv, xv * xv], axis=1)

    xd, md, sd = jnp.asarray(x), jnp.asarray(mask), jnp.asarray(seeds)
    got_k = pb_ops.bootstrap_moments_masked(
        xd, md, sd, B, interpret=False)[..., :3]
    got_j, _ = jax.jit(partial(bootstrap.lane_moment_sums, B=B,
                               use_kernel=False))(xd, md, sd)
    out = {"lanes": {"kernel": max_rel_err(jax.device_get(got_k), ref),
                     "jnp": max_rel_err(jax.device_get(got_j), ref)}}

    # Grouped stream: the packed windows of 3 lanes, interleaved.
    n = w
    gid = rng.integers(0, m, n).astype(np.int32)
    slot = rng.integers(0, w, n).astype(np.int32)
    xs = vals[rng.integers(0, len(vals), n)]
    valid = (rng.random(n) < 0.9).astype(np.float32)
    lane_seed = rng.integers(0, 2**32, m, dtype=np.uint64).astype(np.uint32)
    W = np_poisson1_weights(lane_seed[gid][:, None], slot[:, None],
                            cols[None, :])                    # (n, B)
    xv = xs.astype(np.float64)
    feats = valid[:, None] * np.stack([np.ones(n), xv, xv * xv], axis=1)
    sref = np.stack([W[gid == g].T @ feats[gid == g] for g in range(m)])
    gd, sl, xd, vd, ld = (jnp.asarray(a) for a in
                          (gid, slot, xs, valid, lane_seed))
    got_k = seg_ops.segment_bootstrap_moments(
        gd, sl, xd, vd, ld[gd], m, B, interpret=False)
    got_j, _ = jax.jit(partial(bootstrap.segment_moment_sums, q=m, B=B,
                               use_kernel=False))(xd, gd, sl, vd, ld)
    out["segment"] = {"kernel": max_rel_err(jax.device_get(got_k), sref),
                      "jnp": max_rel_err(jax.device_get(got_j), sref)}

    for path, errs in out.items():
        for impl, e in errs.items():
            ok = bool((e <= MOMENT_RTOL).all())
            shown = ", ".join(f"{v:.3e}" for v in e)
            log(f"  {path:<8} {impl:<6} max rel err [sum w, sum wx, sum wx^2]"
                f" = [{shown}]  rtol {MOMENT_RTOL}"
                f" -> {'ok' if ok else 'FAIL'}")
            if not ok:
                failures.append(f"moment sums {path}/{impl}: max rel err "
                                f"{e.tolist()} > {MOMENT_RTOL}")
    return {p: {i: e.tolist() for i, e in v.items()} for p, v in out.items()}


def run_one_chip(seed: int, scale_factor: float) -> dict:
    import jax

    from repro.aqp import Query, Request
    from repro.data.tpch import make_lineitem
    from repro.serve import AQPSession
    from repro.serve.planner import Route

    failures: list = []
    dev = jax.devices()[0]
    log(f"device_kind: {dev.device_kind}")

    t0 = time.perf_counter()
    data, _ = make_lineitem(scale_factor=scale_factor, group_by="returnflag",
                            seed=seed)
    offsets = np.asarray(data.offsets)
    vals = np.asarray(data.values)[:, 0]
    ex = exact_answers(vals, offsets)
    sess = AQPSession(data, seed=seed)
    jax.block_until_ready(data.values)
    setup_s = time.perf_counter() - t0
    log(f"lineitem SF {scale_factor}: {len(vals):,} rows, "
        f"{data.num_groups} RETURNFLAG groups, "
        f"EXTENDEDPRICE {data.values.nbytes / 2**20:.1f} MiB on device")

    nrm = lambda a: float(np.linalg.norm(a))
    price_gt = ("<", ("lit", PRED_MIN_PRICE), ("col", 0))
    pooled = [
        ("AVG +-2%", Query("avg", epsilon=0.02 * nrm(ex["avg"]), delta=DELTA),
         ex["avg"]),
        ("SUM +-2%", Query("sum", epsilon=0.02 * nrm(ex["sum"]), delta=DELTA),
         ex["sum"]),
        ("COUNT +-2%", Query("count", epsilon=0.02 * nrm(ex["count"]),
                             delta=DELTA), ex["count"]),
        ("VAR +-5% of AVG^2", Query("var", epsilon=0.05 * nrm(ex["avg"]) ** 2,
                                    delta=DELTA), ex["var"]),
        ("AVG GROUP BY +-2%", Query(
            "avg", epsilon=0.02 * float(np.abs(ex["avg"]).min()), delta=DELTA,
            group_by=True), ex["avg"]),
    ]
    host = ("COUNT WHERE price>30k +-5%", Query(
        "count", epsilon=0.05 * nrm(ex["count_pred"]), delta=DELTA,
        predicate=price_gt), ex["count_pred"])

    # Compile + first dispatch: every pooled request admitted in one wave.
    t1 = time.perf_counter()
    rids = {sess.submit(Request(query=q)).rid: (name, q, e)
            for name, q, e in pooled}
    sess.pump()
    first_s = time.perf_counter() - t1
    t2 = time.perf_counter()
    rids[sess.submit(Request(query=host[1])).rid] = host
    responses = sess.drain()
    drain_s = time.perf_counter() - t2
    log(f"wall seconds: setup {setup_s:.3f}  compile+first dispatch "
        f"{first_s:.3f}  drain {drain_s:.3f}")

    st = sess.stats()
    pst = st.get("pool", {})
    log(f"stats: fused_dispatches={st['fused_dispatches']} "
        f"rows_touched={st['rows_touched']} "
        f"steady_recompiles={pst.get('steady_recompiles')} "
        f"pool lanes={pst.get('lanes')} tiers={pst.get('tiers')} "
        f"ticks={pst.get('ticks')}")

    log("answers (float64 full scan as exact):")
    answers = {}
    for r in responses:
        name, q, e = rids.pop(r.rid)
        want = Route.HOST if q.predicate is not None else Route.POOL
        answers[name] = check_answer(name, r, e, q.epsilon, want, failures)
    if rids:
        failures.append(f"unanswered requests: {sorted(rids)}")

    tick_hlo = sess.pool.lowered_tick().as_text()
    kernel_in_tick = "tpu_custom_call" in tick_hlo
    log(f"tick program contains tpu_custom_call: {kernel_in_tick}")
    if not kernel_in_tick:
        failures.append("the pool's tick program has no Pallas kernel")

    log("ESTIMATE moment sums vs float64:")
    moments = check_moments(vals, offsets, seed=seed, failures=failures)

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak}")
    if failures:
        raise SmokeFailure("; ".join(failures))
    return {"setup_s": setup_s, "first_dispatch_s": first_s,
            "drain_s": drain_s, "answers": answers, "moments": moments,
            "peak_bytes_in_use": peak}


# -- four chips --------------------------------------------------------------

def run_four_chips(seed: int, scale_factor: float) -> dict:
    import jax

    from repro.aqp import Query, Request
    from repro.core.sampling import root_key
    from repro.data.tpch import make_lineitem
    from repro.serve import AQPSession
    from repro.serve.planner import Route

    failures: list = []
    devs = jax.devices()
    if len(devs) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    log(f"device_kind: {devs[0].device_kind} x {len(devs)}")

    t0 = time.perf_counter()
    data, _ = make_lineitem(scale_factor=scale_factor, group_by="returnflag",
                            seed=seed)
    offsets = np.asarray(data.offsets)
    vals = np.asarray(data.values)[:, 0]
    ex = exact_answers(vals, offsets)
    setup_s = time.perf_counter() - t0
    log(f"lineitem SF {scale_factor}: {len(vals):,} rows, "
        f"{data.num_groups} RETURNFLAG groups")

    nrm = lambda a: float(np.linalg.norm(a))
    mix = [(f"{f.upper()} +-{p:g}%", Query(f, epsilon=p / 100 * nrm(ex[f]),
                                            delta=DELTA), ex[f])
           for f in ("avg", "sum", "count") for p in (2, 3)]
    keys = jax.random.split(root_key(seed), len(mix))

    drains, timing = {}, {}
    for label, mesh in (("mesh", None), ("one_device", False)):
        t1 = time.perf_counter()
        sess = AQPSession(data, seed=seed, data_shards=4, mesh=mesh)
        for (_, q, _), k in zip(mix, keys):
            sess.submit(Request(query=q), key=k)
        drains[label] = sess.drain()
        timing[label] = time.perf_counter() - t1
        off_pool = [r.rid for r in drains[label] if r.route != Route.POOL]
        if off_pool:
            failures.append(f"{label}: requests {off_pool} not on POOL")
        if label == "mesh":
            pool = sess.pool
            shard_devs = sorted(d.id for d in pool.values.sharding.device_set)
            shard_bytes = pool.values.nbytes // 4
            mem = [d.memory_stats() or {} for d in devs[:4]]
            per_dev = [s.get("bytes_in_use") for s in mem]
            peaks = [s.get("peak_bytes_in_use") for s in mem]
            log(f"mesh pool values sharding: {pool.values.sharding} on "
                f"devices {shard_devs}, {shard_bytes:,} bytes per shard")
            log(f"per-device bytes_in_use {per_dev}  peak {peaks}")
            if len(shard_devs) != 4 or any(
                    b is None or b < shard_bytes for b in per_dev):
                failures.append(
                    f"values not on four devices: {shard_devs} {per_dev}")
            st = sess.stats()
            log(f"stats: fused_dispatches={st['fused_dispatches']} "
                f"rows_touched={st['rows_touched']} "
                f"steady_recompiles={st['pool']['steady_recompiles']} "
                f"shard_rows={st['pool']['shard_rows']}")
            hlo = pool.lowered_tick().as_text()
            log(f"mesh tick program: tpu_custom_call "
                f"{'tpu_custom_call' in hlo}, all-reduce "
                f"{'all_reduce' in hlo or 'all-reduce' in hlo}")
    log(f"wall seconds: setup {setup_s:.3f}  mesh drain "
        f"{timing['mesh']:.3f}  one-device drain {timing['one_device']:.3f}")

    log("answers (float64 full scan as exact):")
    bitwise, theta_rel = True, 0.0
    for (name, q, e), a, b in zip(mix, drains["mesh"], drains["one_device"]):
        for label, r in (("mesh", a), ("one_device", b)):
            d = float(np.linalg.norm(np.asarray(r.theta, np.float64).ravel()
                                     - e))
            ok = d <= q.epsilon and bool(r.success)
            log(f"  {name:<10} {label:<10} success={r.success} "
                f"l2 distance {d:.6g} epsilon {q.epsilon:.6g} "
                f"-> {'ok' if ok else 'FAIL'}")
            if not ok:
                failures.append(f"{name} ({label}): distance {d} epsilon "
                                f"{q.epsilon} success {r.success}")
        ta = np.asarray(a.theta, np.float32).ravel()
        tb = np.asarray(b.theta, np.float32).ravel()
        same = (ta.tobytes() == tb.tobytes()
                and np.float32(a.error) == np.float32(b.error)
                and np.array_equal(np.ravel(a.n), np.ravel(b.n)))
        bitwise &= bool(same)
        theta_rel = max(theta_rel, float(np.max(
            np.abs(ta.astype(np.float64) - tb) / np.abs(tb))))
    parity = "bitwise" if bitwise else f"max theta rel diff {theta_rel:.3g}"
    log(f"mesh vs one-device drain: {parity}")
    if failures:
        raise SmokeFailure("; ".join(failures))
    return {"parity": parity, "setup_s": setup_s, **timing}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    src = HERE / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    dev = device_summary()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']!r}); "
              f"this script runs on the chip only", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    try:
        if args.chips == 4:
            run_four_chips(args.seed, scale_factor=40)
        else:
            run_one_chip(args.seed, scale_factor=10)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
