"""Benchmark sections: one per paper table/figure, plus kernels and serving.

    PYTHONPATH=src python -m benchmarks.run            # standard pass
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
    PYTHONPATH=src python -m benchmarks.run --only fig3
    PYTHONPATH=src python -m benchmarks.run --only fused --json
    PYTHONPATH=src python -m benchmarks.run --only serve,distributed \
        --devices 4 --json

Prints ``name,us_per_call,derived`` CSV rows (skeleton contract); ``--json``
additionally writes ``BENCH_fused.json`` / ``BENCH_serve.json`` with
machine-readable rows for the fused / serve+distributed sections, so the
perf trajectory stays comparable across PRs.

``--devices N`` simulates an N-device host mesh
(``--xla_force_host_platform_device_count``) for the distributed section;
it must take effect before jax is imported, which is why every section
import in this module is lazy.
"""
from __future__ import annotations

import argparse
import json
import os

SERVE_JSON_KEYS = (
    "bench", "us_per_call", "rows_touched", "dispatches", "speedup_vs_loop",
    "active_frac", "rows_per_tick", "p50_ms", "p95_ms", "p99_ms", "slo_miss",
    "queries", "lanes", "data_shards", "qps", "speedup_vs_1dev",
    "shard_rows", "parity_bitwise_vs_1dev", "parity_solo_fused_l2miss",
    "hit_rate", "dispatches_per_query", "warm_speedup_p50", "cache_served",
    "warm_verify_failures", "num_groups", "speedup_vs_indep",
    "rows_scanned_block", "rows_scanned_indep", "rows_ratio", "parity_exact",
    "parity_theta", "parity_error", "rare_group_ok",
    "offered_load", "rate_qps", "achieved_qps", "deadline_ms",
    "shed", "degraded", "migrations", "contract_ok")


def _run_fig1(emit, args):
    from . import bench_applicability
    bench_applicability.run(emit, full=args.full, trials=args.trials)


def _run_fig2(emit, args):
    from . import bench_applicability
    bench_applicability.run_multigroup(emit, full=args.full,
                                       trials=args.trials)


def _run_fig3(emit, args):
    from . import bench_efficiency
    bench_efficiency.run(emit, full=args.full, trials=args.trials)


def _run_fig4(emit, args):
    from . import bench_ordering
    bench_ordering.run(emit, full=args.full, trials=args.trials)


def _run_kern(emit, args):
    from . import bench_kernels
    bench_kernels.run(emit, full=args.full)


def _run_store(emit, args):
    from . import bench_sample_store
    bench_sample_store.run(emit, full=args.full)


def _run_fused(emit, args):
    from . import bench_fused
    bench_fused.run(emit, full=args.full)


def _run_serve(emit, args):
    from . import bench_serve_pool
    bench_serve_pool.run(emit, full=args.full, smoke=args.smoke,
                         arrivals=args.arrivals,
                         offered_load=args.offered_load)


def _run_overload(emit, args):
    from . import bench_serve_pool
    bench_serve_pool.run_overload(emit, full=args.full, smoke=args.smoke,
                                  offered_load=args.offered_load)


def _run_distributed(emit, args):
    from . import bench_serve_pool
    bench_serve_pool.run_sharded(emit, full=args.full, smoke=args.smoke,
                                 devices=args.devices)


def _run_cache(emit, args):
    from . import bench_serve_pool
    bench_serve_pool.run_cache(emit, full=args.full, smoke=args.smoke)


def _run_groupby(emit, args):
    from . import bench_serve_pool
    bench_serve_pool.run_groupby(emit, full=args.full, smoke=args.smoke)


# The full section registry; --only names are validated against it.
SECTIONS = {
    "fig1": _run_fig1,
    "fig2": _run_fig2,
    "fig3": _run_fig3,
    "fig4": _run_fig4,
    "kern": _run_kern,
    "store": _run_store,
    "fused": _run_fused,
    "serve": _run_serve,
    "distributed": _run_distributed,
    "cache": _run_cache,
    "groupby": _run_groupby,
    "overload": _run_overload,
}


def parse_sections(only: "str | None") -> "list[str]":
    """``--only`` value -> validated section list (None -> all sections)."""
    if only is None:
        return list(SECTIONS)
    names = [s.strip() for s in only.split(",") if s.strip()]
    unknown = [s for s in names if s not in SECTIONS]
    if unknown or not names:
        raise SystemExit(
            f"unknown section(s) {unknown or [only]!r}; "
            f"registry: {', '.join(SECTIONS)}")
    return names


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale data sizes (slow on CPU)")
    ap.add_argument("--only", default=None, metavar="SECTION[,SECTION...]",
                    help=f"run selected sections (default: all); "
                         f"registry: {', '.join(SECTIONS)}")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<section>.json "
                         "(fused / serve / distributed sections)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs "
                         "(serve / distributed sections)")
    ap.add_argument("--arrivals", default=None, choices=("poisson",),
                    help="also run the open-loop serve benchmark with this "
                         "arrival process (serve section: seeded Poisson "
                         "arrivals, p50/p95/p99 latency, SLO-miss rate)")
    ap.add_argument("--offered-load", type=float, default=None,
                    metavar="FRAC",
                    help="offered load as a fraction of measured capacity, "
                         "shared by the poisson open-loop bench (default "
                         "0.6) and the overload section (default sweep "
                         "1.0,1.5)")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="simulate an N-device host mesh for the "
                         "distributed section (sets XLA_FLAGS before jax "
                         "loads; ignored if jax is already imported)")
    ap.add_argument("--trials", type=int, default=40,
                    help="simulated-confidence trials")
    args = ap.parse_args()
    sections = parse_sections(args.only)
    if args.devices:
        import sys
        flag = f"--xla_force_host_platform_device_count={int(args.devices)}"
        if "jax" in sys.modules:
            print(f"warning: --devices ignored (jax already imported; "
                  f"set XLA_FLAGS={flag} in the environment)", flush=True)
        else:
            prev = os.environ.get("XLA_FLAGS", "")
            os.environ["XLA_FLAGS"] = f"{prev} {flag}".strip()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from .common import CsvEmitter
    emit = CsvEmitter()
    emit.header()
    wrote_json = False
    for name in sections:
        SECTIONS[name](emit, args)
        if not args.json:
            continue
        if name == "fused":
            with open("BENCH_fused.json", "w") as fh:
                json.dump(emit.json_rows("fused/"), fh, indent=2)
            print("wrote BENCH_fused.json", flush=True)
            wrote_json = True
    if args.json and any(s in sections
                         for s in ("serve", "distributed", "cache",
                                   "groupby", "overload")):
        # serve + distributed + cache + groupby + overload share one
        # artifact (all emit serve/ rows); written once, after every
        # selected section.
        with open("BENCH_serve.json", "w") as fh:
            json.dump(emit.json_rows("serve/", keys=SERVE_JSON_KEYS),
                      fh, indent=2)
        print("wrote BENCH_serve.json", flush=True)
        wrote_json = True
    if args.json and not wrote_json:
        print("warning: --json only applies to the fused/serve/distributed "
              "sections (use --only fused / --only serve,distributed or "
              "run all sections)", flush=True)


if __name__ == "__main__":
    main()
