"""One run of one benchmark cell: serve a traffic mix through ``AQPSession``.

    python3 aqpbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell names a configuration
(``aqpbench/configs/<config>.json``: the table and its answer contract) and
a traffic mix (``aqpbench/workloads/<cell>.json``).  A run:

1. refuses to run unless JAX's first device is a TPU listed in
   ``aqpbench/peaks.json`` (``--rehearse`` admits the CPU at a tiny
   ``--rows`` and never prints the result line);
2. keeps JAX's compilation cache in ``$JAX_COMPILATION_CACHE_DIR`` or
   ``.jax_cache/`` at the checkout root;
3. makes the table on the device from ``--seed``, and its exact answers;
4. builds the session with the contract's ``B`` and the default knobs;
   a configuration with ``"data_shards": S`` (absent: 1; at most the
   cell's ``chips``) gets its table row-sharded over the first S devices
   and a session with ``data_shards=S`` on the program's default mesh;
5. warms up with the cell's own traffic, counted as set-up;
6. measures for ``--seconds`` (with ``--trace 1``, the first
   ``TRACE_SECONDS`` under the profiler, and the untraced rest after the
   profiler has stopped), then answers every request still in flight;
7. checks every answer of the window against its own contract (success,
   error bar within epsilon), recomputes a seed-drawn sample of them in
   float64, and prints one JSON result line last on standard output.  The
   reference follows the layout the lane pool reports: one shard
   (``aqpbench/reference.py``, the pool's ``TRAJECTORY``), or S row
   shards (``aqpbench/reference_sharded.py``, ``TRAJECTORY_SHARDED``:
   the proportional-emission slot merge, each shard's own slot binding
   and bootstrap streams, the shards' sums added in float64).

End-to-end metrics (``--trace 0``) and per-layer metrics (``--trace 1``)
are the ``BENCHMARK.json`` entries that apply to the cell; each is read from
the run record by ``aqpbench/metrics/<name>.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
DRAIN_GRACE_S = 60.0        # an answer may come this late after the close
WARMUP_LIMIT_S = 600.0      # warm-up gives up waiting for answers here
TRACE_SECONDS = 3.0         # traced part of a --trace 1 window
SAMPLE_SOLO, SAMPLE_GROUPED = 32, 12   # fused answers recomputed per run
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
FUSED_ROUTES = ("pool", "loop", "batched")
TRAJECTORY = ("B", "n_min", "n_max", "n_cap", "max_iters", "l", "ext_cap")
TRAJECTORY_SHARDED = ("B", "n_min", "n_max", "n_cap", "max_iters", "l",
                      "seg_window", "data_shards")


class RunError(Exception):
    """The run cannot produce a result (exit code 2, no result line)."""


def since_start() -> float:
    return time.perf_counter() - T_PROCESS


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Rec:
    """One request as the client saw it."""
    item: object
    submitted: float
    rid: int
    done: Optional[float] = None
    resp: object = None


class Client:
    """Drives one session with a closed loop of ``clients`` clients, each
    submitting its next request once its last is answered; records spans."""

    def __init__(self, sess, items, make_request, workload):
        self.sess, self.items, self.make_request = sess, items, make_request
        self.clients = int(workload["clients"])
        self.inflight: Dict[int, Rec] = {}
        self.done: List[Rec] = []
        self.spans: List[tuple] = []      # (label, start, end)
        self.record_spans = False
        self.annotate = None              # jax.profiler.TraceAnnotation
        self.accepting = True

    @contextlib.contextmanager
    def span(self, label: str):
        t0 = time.perf_counter()
        if self.annotate is not None:
            with self.annotate("aqpbench." + label):
                yield
        else:
            yield
        if self.record_spans:
            self.spans.append((label, t0, time.perf_counter()))

    def _submit(self) -> None:
        item = next(self.items)
        req = self.make_request(item)
        t = time.perf_counter()
        self.sess.submit(req, key=item.key)
        self.inflight[req.rid] = Rec(item, t, req.rid)

    def _step(self) -> None:
        with self.span("pump"):
            self.sess.pump()
        with self.span("poll"):
            for rid in list(self.inflight):
                r = self.sess.poll(rid)
                if r is not None:
                    rec = self.inflight.pop(rid)
                    rec.done, rec.resp = time.perf_counter(), r
                    self.done.append(rec)

    def run(self, until: float = np.inf, answers: Optional[int] = None
            ) -> None:
        """Serve until ``until`` or until ``answers`` more have finished."""
        target = None if answers is None else len(self.done) + answers
        while time.perf_counter() < until and (
                target is None or len(self.done) < target):
            if self.accepting and len(self.inflight) < self.clients:
                with self.span("submit"):
                    while len(self.inflight) < self.clients:
                        self._submit()
            self._step()

    def finish(self, grace_s: float) -> None:
        """Stop taking new requests; serve what is in flight."""
        self.accepting = False
        end = time.perf_counter() + grace_s
        while self.inflight and time.perf_counter() < end:
            self._step()


def _device_ids(x) -> List[int]:
    return sorted(d.id for d in x.sharding.device_set)


def _by_device(devs, stat: str) -> List[int]:
    return [int((d.memory_stats() or {}).get(stat, 0)) for d in devs]


def load_metric(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "aqpbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def contract_misses(window) -> int:
    """Answers that did not meet the request's own contract: not
    successful, an error bar above the requested epsilon (for GROUP BY, in
    any group), or answered shed or degraded."""
    misses = 0
    for r in window:
        resp, eps = r.resp, r.item.epsilon
        if resp.group_by:
            errs = np.asarray(resp.group_error, np.float64)
            ok = bool(np.all(resp.group_success)) and bool(np.all(errs <= eps))
        else:
            ok = bool(resp.success) and float(resp.error) <= eps
        misses += not (ok and not resp.shed and not resp.degraded)
    return misses


def _fmt(xs) -> str:
    return "[" + ", ".join(f"{x:.3e}" for x in xs) + "]"


def _gap_numbers(theta: List[float], errbar: List[float]) -> Dict[str, float]:
    """Largest and median gap over the checked lanes (0 where none)."""
    out = {}
    for name, v in (("theta_gap", theta), ("errbar_gap", errbar)):
        out[name] = float(max(v, default=0.0))
        out[name + "_median"] = float(np.median(v)) if v else 0.0
    return out


def check_answers(client: Client, t_start: float, exact, ref, epochs: int,
                  seed: int, delta: float, limits: Dict,
                  control: bool = False,
                  routes=FUSED_ROUTES) -> Dict[str, Dict]:
    """The numbers compared, each with its value and limit.

    With ``control`` the bfloat16 control's gaps (the reference on bfloat16
    operands, put in the program's place) are compared instead of the
    program's, so a run reads not correct exactly where the control would.
    Answers of the fused ``routes`` are the ones ``ref`` recomputes.
    """
    from aqpbench.reference import Served
    from aqpbench.traffic import _rng

    window = [r for r in client.done if r.done >= t_start]
    misses, verdicts = 0, 0
    for r in window:
        it, resp = r.item, r.resp
        truth = exact[it.func]
        theta = np.asarray(resp.theta, np.float64).ravel()
        if it.group_by:     # one (epsilon, delta) contract per group
            misses += int(np.sum(np.abs(theta - truth) > it.epsilon))
            verdicts += len(theta)
        else:
            misses += float(np.linalg.norm(theta - truth)) > it.epsilon
            verdicts += 1
    fused = [r for r in window if r.resp.route.value in routes
             and not r.resp.shed]
    others = sum(1 for r in window if r.resp.route.value in FUSED_ROUTES
                 and r.resp.route.value not in routes)
    if others:
        log(f"{others} fused answers of the window on routes other than "
            f"{list(routes)}: the reference does not follow them")
    # The sample: the answer with the most rows, then answers drawn from the
    # seed without replacement.
    rng = _rng(seed, 0xC4EC)
    picks = []
    for grouped, count in ((False, SAMPLE_SOLO), (True, SAMPLE_GROUPED)):
        pool = [r for r in fused if r.item.group_by == grouped]
        if not pool:
            continue
        big = max(pool, key=lambda r: int(np.sum(r.resp.n)))
        rest = [r for r in pool if r is not big]
        picks.append(big)
        picks += [rest[i] for i in sorted(rng.permutation(len(rest))
                                          [:count - 1])]
    lanes = {k: [] for k in ("theta", "errbar", "ctrl_theta", "ctrl_errbar")}
    t0 = time.perf_counter()
    for r in picks:
        resp = r.resp
        err = (resp.group_error if resp.group_by else [resp.error])
        c = ref.check(Served(
            func=r.item.func, delta=delta, key=r.item.key,
            theta=np.asarray(resp.theta, np.float64).ravel(),
            error=np.asarray(err, np.float64).ravel(),
            n=np.asarray(resp.n).ravel().astype(np.int64),
            group_by=bool(resp.group_by)), epochs, control=control)
        for k in lanes:
            lanes[k] += getattr(c, k + "_gaps")
        log(f"  checked {r.item.func:<5} grouped={resp.group_by!s:<5} "
            f"route={resp.route.value:<5} n={np.asarray(resp.n).ravel().tolist()}"
            f" epoch={c.epoch} ticks={c.ticks} by lane: theta_gap "
            f"{_fmt(c.theta_gaps)} errbar_gap {_fmt(c.errbar_gaps)} control "
            f"{_fmt(c.ctrl_theta_gaps)} {_fmt(c.ctrl_errbar_gaps)}")
    log(f"reference: {len(picks)} answers, {len(lanes['theta'])} lanes in "
        f"{time.perf_counter() - t0:.3f} s")
    gaps = _gap_numbers(lanes["theta"], lanes["errbar"])
    log(f"program: {gaps}")
    if control:
        gaps = _gap_numbers(lanes["ctrl_theta"], lanes["ctrl_errbar"])
        log(f"control (bfloat16 reference in the program's place): {gaps}; "
            f"these are the numbers compared")
    # Coverage against the exact full scan is reported, not compared: the
    # answers of one sample epoch share their rows, so whether an answer's
    # truth lies within epsilon is a delta-level verdict that no precision
    # control moves.
    log(f"coverage: {misses} of {verdicts} (answer, group) verdicts outside "
        f"epsilon of the float64 full scan (miss share "
        f"{misses / max(verdicts, 1)!r}; delta {delta})")
    values = {**gaps, "checked_answers": float(len(picks)),
              "contract_misses": float(contract_misses(window))}
    out = {}
    for name, lim in limits.items():
        v = values[name]
        ok = v >= lim if name == "checked_answers" else v <= lim
        out[name] = {"value": v, "limit": lim, "ok": bool(ok)}
    return out


def run(args):
    if not (ROOT / "src" / "repro").is_dir():
        raise RunError(f"no src/repro under {ROOT}: run from a checkout of "
                       f"the repository")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise RunError(f"unknown workload {args.workload!r}")
    cell = cells[args.workload]
    workload = load_json(HERE / "workloads" / f"{args.workload}.json")
    config = load_json(HERE / "configs" / f"{cell['config']}.json")
    if workload.get("loop") != "closed":
        raise RunError(f"workload loop {workload.get('loop')!r}: only "
                       f"closed loops are driven")
    shards = int(config.get("data_shards", 1))
    if not 1 <= shards <= int(cell["chips"]):
        raise RunError(f"configuration {cell['config']!r} asks for "
                       f"data_shards={shards}; the cell has {cell['chips']} "
                       f"chips")

    import jax

    devs = jax.devices()
    dev = devs[0]
    peaks = load_json(HERE / "peaks.json")["devices"]
    if dev.device_kind not in peaks:
        raise RunError(f"device kind {dev.device_kind!r} is not in "
                       f"aqpbench/peaks.json")
    rehearsal = bool(peaks[dev.device_kind].get("rehearsal"))
    if rehearsal and not args.rehearse:
        raise RunError(f"JAX found no accelerator (platform "
                       f"{dev.platform!r}); this benchmark runs on the chip")
    if not rehearsal and dev.platform != "tpu":
        raise RunError(f"platform {dev.platform!r} is not a TPU")
    if len(devs) < int(cell["chips"]):
        raise RunError(f"cell needs {cell['chips']} chips, JAX sees "
                       f"{len(devs)}")
    if not rehearsal:
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
            ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    from repro.aqp import Query, Request
    from repro.core.sampling import GroupedData
    from repro.serve import AQPSession

    from aqpbench import reference, tpch, traffic

    seed = int(args.seed)
    contract = config["contract"]
    values, offsets = tpch.make_lineitem(
        config, seed, rows=args.rows if rehearsal else None)
    host = np.asarray(jax.device_get(values))[:, 0]
    exact = tpch.exact_answers(host, offsets)
    var = traffic.variants(workload, exact)
    log(f"[{since_start():.3f} s] table: {int(offsets[-1]):,} rows, groups "
        f"{np.diff(offsets).tolist()}, {host.nbytes / 2**20:.1f} MiB on "
        f"{dev.device_kind} devices {_device_ids(values)}; {len(var)} query "
        f"variants")

    def make_request(item) -> Request:
        return Request(query=Query(
            item.func, epsilon=float(item.epsilon),
            delta=float(contract["delta"]), group_by=item.group_by))

    data = GroupedData(values, offsets)
    # The session's knobs stay at their defaults; the contract's B and a
    # sample seed drawn from ``--seed`` are passed.  A CPU rehearsal runs the
    # chip's Pallas ESTIMATE kernels (interpreted) rather than the jnp
    # ESTIMATE that the default picks off a TPU, whose float32 sums round
    # differently, so that the limits set on the chip apply to it.
    sess = AQPSession(data, B=int(contract["B"]), seed=seed % (2 ** 31 - 1),
                      **({"use_kernel": True} if rehearsal else {}),
                      **({"data_shards": shards} if shards > 1 else {}))
    client = Client(sess, traffic.stream(var, seed), make_request, workload)
    client.run(answers=int(workload.get("warmup", {}).get("answers", 32)),
               until=time.perf_counter() + WARMUP_LIMIT_S)
    log(f"[{since_start():.3f} s] warmed with the cell's traffic "
        f"({len(client.done)} answers, {compiles[0]} compilations so far)")
    if shards > 1 and sess.pool is not None:
        log(f"pool values: {sess.pool.values.shape} on devices "
            f"{_device_ids(sess.pool.values)}; bytes in use by device "
            f"{_by_device(devs[:shards], 'bytes_in_use')}")

    # -- the measured window
    trace_dir = tempfile.mkdtemp(prefix="aqpbench-trace-") if args.trace \
        else None
    st0 = sess.stats()
    c0 = compiles[0]
    client.record_spans = True
    t_start = time.perf_counter()
    setup_s = t_start - T_PROCESS
    if trace_dir:
        # Host tracing at level 1 keeps the benchmark's annotations and
        # leaves out the Python tracer, which slows the host severalfold.
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level, opts.python_tracer_level = 1, 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        client.annotate = jax.profiler.TraceAnnotation
        client.run(until=t_start + min(TRACE_SECONDS, args.seconds / 2))
        t_trace = time.perf_counter()
        traced_answers = sum(1 for r in client.done
                             if t_start <= r.done <= t_trace)
        jax.profiler.stop_trace()
        client.annotate = None
        # The host's per-layer numbers come from the untraced rest, which
        # keeps its planned length however long the profiler takes to stop
        # (tens of seconds for four chips' planes).
        t_resume = time.perf_counter()
        st_resume = sess.stats()
        t_end = t_resume + args.seconds - (t_trace - t_start)
    else:
        t_end = t_start + args.seconds
    client.run(until=t_end)
    t_end = time.perf_counter()
    st1 = sess.stats()
    c_window = compiles[0] - c0
    in_window = [r for r in client.done if t_start <= r.done <= t_end]
    attempted = len([r for r in client.done + list(client.inflight.values())
                     if r.submitted < t_end and (r.done is None
                                                 or r.done >= t_start)])
    client.finish(DRAIN_GRACE_S)
    unanswered = len(client.inflight)
    log(f"window: {t_end - t_start:.3f} s, {len(in_window)} answers, "
        f"compilations inside the window {c_window}, steady_recompiles "
        f"{st1.get('pool', {}).get('steady_recompiles')}")
    routes: Dict[str, int] = {}
    for r in in_window:
        routes[r.resp.route.value] = routes.get(r.resp.route.value, 0) + 1
    log(f"routes in the window: {routes}; pool {st1.get('pool', {}).get('lanes')} "
        f"lanes, {st1.get('pool_rebuilds')} rebuilds, sample epoch "
        f"{st1['sample_epoch']}, slots held by shard "
        f"{st1.get('pool', {}).get('shard_rows')}")
    peaks = _by_device(devs[:int(cell["chips"])], "peak_bytes_in_use")
    peak = max(peaks)
    log(f"peak bytes in use by device {peaks}")

    record = {
        "cell": args.workload, "loop": workload["loop"],
        "setup_s": setup_s, "window_s": t_end - t_start,
        "answers": len(in_window),
        "rows_touched": st1["rows_touched"] - st0["rows_touched"],
        "compiles_in_window": c_window,
    }
    if trace_dir:
        from aqpbench import trace_reduce
        record.update({
            "traced_s": t_trace - t_start,
            "traced_answers": traced_answers,
            "rest_answers": sum(1 for r in client.done
                                if t_resume <= r.done <= t_end),
            "pump_s": [b - a for lab, a, b in client.spans
                       if lab == "pump" and t_resume <= a and b <= t_end],
            "pool_ticks": (st1.get("pool", {}).get("ticks", 0)
                           - st_resume.get("pool", {}).get("ticks", 0)),
        })
        ex = trace_reduce.extract(trace_reduce.find_xplane(trace_dir))
        record["trace"] = trace_reduce.reduce(ex)
        # The metrics average over the device planes; each plane alone:
        log("trace: busy seconds by device plane " + str({
            plane: (trace_reduce.reduce({"host": ex["host"],
                                         "device": {plane: evs}})
                    or {}).get("busy_s")
            for plane, evs in sorted(ex["device"].items())}))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if record["trace"] is None:
            if not rehearsal:
                raise RunError("the traced window holds no device operation")
            record["trace"] = {"window_s": record["traced_s"], "busy_s": 0.0,
                               "estimate_kernel_s": 0.0, "idle_by_label": {},
                               "device_ops": [], "idle_gaps": []}
        log(f"trace: busy {record['trace']['busy_s']:.6f} s of "
            f"{record['trace']['window_s']:.6f} s; idle by host span "
            f"{record['trace']['idle_by_label']}")
        log(f"trace: top device ops {record['trace']['device_ops']}")

    # -- correctness, after the program's state is freed
    # The reference follows the trajectory the session ran: its sample
    # seed, the lane pool's layout and its recorded step parameters.
    spec = getattr(sess.pool, "_spec", None) or {}
    sharded = int(spec.get("data_shards", 1)) > 1
    keys = TRAJECTORY_SHARDED if sharded else TRAJECTORY
    missing = [k for k in keys if k not in spec]
    if missing:
        raise RunError(f"the session's lane pool records no {missing}")
    traj = {k: int(spec[k]) for k in keys}
    sess_seed = int(sess.seed)
    epochs = int(sess.stats()["sample_epoch"])
    del sess, data, values
    client.sess = None
    gc.collect()
    log(f"trajectory: session seed {sess_seed}, {traj}")
    if sharded:
        from aqpbench.reference_sharded import ShardedReference
        ref = ShardedReference(host, offsets, session_seed=sess_seed, **traj)
        log(f"sharded layout: {ref.rows_per_shard:,} rows a shard, slot "
            f"capacity by group {ref.cap.tolist()}")
    else:
        ref = reference.Reference(host, offsets, session_seed=sess_seed,
                                  **traj)
    log(f"[{since_start():.3f} s] program state freed; checking answers")
    checks = check_answers(client, t_start, exact, ref, epochs,
                           seed, float(contract["delta"]), workload["limits"],
                           control=args.control,
                           routes=("pool",) if sharded else FUSED_ROUTES)
    checks["unanswered"] = {"value": float(unanswered), "limit": 0.0,
                            "ok": unanswered == 0}
    correct = all(c["ok"] for c in checks.values())

    metrics = {}
    kind = "per_layer" if args.trace else "end_to_end"
    for entry in bench[kind]:
        if not applies(entry, args.workload):
            continue
        v = load_metric(entry["name"])(record)
        if v is not None:
            metrics[entry["name"]] = {"value": float(v), "unit": entry["unit"]}
    result = {
        "correct": bool(correct), "attempted": attempted,
        "failed": unanswered, "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak},
    }
    if args.trace:
        result["device"]["busy_s"] = record["trace"]["busy_s"]
        result["device"]["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": record["trace"]["device_ops"],
            "idle_gaps": record["trace"]["idle_gaps"]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    log(f"host peak RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}"
        f" KiB")
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAIL'}")
    return result, rehearsal


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="admit the CPU at --rows rows; no result line")
    ap.add_argument("--rows", type=int, default=300_000)
    ap.add_argument("--control", action="store_true",
                    help="compare the bfloat16 control's gaps in the program's "
                    "place (it has to read not correct)")
    args = ap.parse_args(argv)
    try:
        result, rehearsal = run(args)
    except RunError as e:
        log(f"aqpbench: {e}")
        return 2
    if rehearsal:
        log(f"aqpbench: rehearsal finished, correct={result['correct']}; "
            f"no result line")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
