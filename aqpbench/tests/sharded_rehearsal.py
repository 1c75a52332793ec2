"""One CPU rehearsal of a row-sharded cell on four host devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python aqpbench/tests/sharded_rehearsal.py [--control] [--fault F]

Drives a whole run of the harness (``--rehearse``: the look for a chip is
skipped, the chip's Pallas kernels are interpreted) on a test-only cell: the
configuration ``tpch-test-returnflag-4shard.json`` beside this file (TPC-H
lineitem by L_RETURNFLAG, ``data_shards`` 4, four chips) under cell 1's
traffic and limits.  ``--fault`` breaks the timed path underneath:

* ``psum_dropped``: the exchange between chips left out; each device keeps
  its own shard's moment sums;
* ``state_unchanged``: the sharded step returns its state unchanged;
* ``half_window``: half of each segment's window left out of the estimate;
* ``answer_altered``: every estimate scaled by 1 + 1e-4 where the step
  produces it.

Prints the run's ``correct`` and its numbers compared as one JSON line on
standard output.  ``test_sharded.py`` runs
it in a subprocess, so that the test process keeps its one device.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

CELL = {"name": "test-sharded-solo-closed",
        "config": "tpch-test-returnflag-4shard", "traffic": "solo-closed",
        "chips": 4}
TRAFFIC = ROOT / "aqpbench" / "workloads" / "sf10-returnflag-solo-closed.json"
FAULTS = ("psum_dropped", "state_unchanged", "half_window", "answer_altered")


def install(harness, cell=CELL, config=None) -> None:
    """Make the harness find the test cell, its configuration (``config``
    in place of the file's, if given) and cell 1's traffic file."""
    real = harness.load_json

    def load_json(path):
        path = Path(path)
        if path == ROOT / "BENCHMARK.json":
            bench = real(path)
            bench["workloads"] = bench["workloads"] + [cell]
            return bench
        if path.name == f"{cell['name']}.json":
            return real(TRAFFIC)
        if path.name == f"{cell['config']}.json":
            return config if config is not None else real(HERE / path.name)
        return real(path)

    harness.load_json = load_json


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import bootstrap, fused
    from repro.serve import lane_pool

    if fault == "psum_dropped":
        jax.lax.psum = lambda x, axis_name, **kw: x
    elif fault == "state_unchanged":
        lane_pool.make_sharded_step = (
            lambda mesh, **kw: lambda values, state, params, spec: state)
    elif fault == "half_window":
        real = bootstrap.lane_moment_sums

        def half(v, mf, seeds, B, **kw):
            count = jnp.sum(mf, axis=-1, keepdims=True)
            keep = jnp.cumsum(mf, axis=-1) <= jnp.ceil(count / 2)
            return real(v, mf * keep, seeds, B, **kw)
        bootstrap.lane_moment_sums = half
    elif fault == "answer_altered":
        real = fused._lane_epilogue

        def altered(s, p, **kw):
            kw["theta_b"] = kw["theta_b"] * (1.0 + 1e-4)
            return real(s, p, **kw)
        fused._lane_epilogue = altered
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--seed", type=int, default=2 ** 33 + 17)
    ap.add_argument("--rows", type=int, default=300_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from aqpbench import run as harness

    install(harness)
    if args.fault:
        plant(args.fault)
    harness.DRAIN_GRACE_S = 30.0
    harness.WARMUP_LIMIT_S = 20.0
    # The CPU recomputes an answer several times slower than the chip.
    harness.SAMPLE_SOLO = 8
    result, rehearsal = harness.run(argparse.Namespace(
        workload=CELL["name"], seed=args.seed, seconds=args.seconds,
        trace=0, rehearse=True, rows=args.rows, control=args.control))
    assert rehearsal
    print(json.dumps({"correct": result["correct"],
                      "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
