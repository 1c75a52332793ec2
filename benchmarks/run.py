"""The paper's statistical figures: applicability, efficiency, ordering.

    PYTHONPATH=src python -m benchmarks.run            # all four figures
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
    PYTHONPATH=src python -m benchmarks.run --only fig3

Prints ``name,us_per_call,derived`` CSV rows.  These reproduce the paper's
figures (sample sizes, simulated confidence, ordering accuracy); they are
not speed records.  The speed benchmark is ``aqpbench/`` (BENCHMARK.json).
"""
from __future__ import annotations

import argparse


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


# The full section registry; --only names are validated against it.
SECTIONS = {
    "fig1": _run_fig1,
    "fig2": _run_fig2,
    "fig3": _run_fig3,
    "fig4": _run_fig4,
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
    ap.add_argument("--trials", type=int, default=40,
                    help="simulated-confidence trials")
    args = ap.parse_args()
    sections = parse_sections(args.only)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from .common import CsvEmitter
    emit = CsvEmitter()
    emit.header()
    for name in sections:
        SECTIONS[name](emit, args)


if __name__ == "__main__":
    main()
