"""The harness on a row-sharded configuration, at a tiny size on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q aqpbench/tests/test_sharded.py

Each whole run (``sharded_rehearsal.py``) goes in a subprocess with four
host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``), so
that this process keeps its one device:

* the sound program reads ``correct`` against ``ShardedReference``; with
  ``--control`` (the reference on bfloat16 operands in the program's place)
  the run reads not correct, and the control fails both recomputation
  limits;
* with the timed path broken underneath, ``correct`` comes out false for
  each fault a sharded serving cell can have: the exchange between chips
  (the moment ``psum``) left out, a step that returns its state unchanged,
  half of each segment's window left out of the estimate, and an answer
  altered where the step produces it.

In this process: a configuration whose ``data_shards`` exceeds the cell's
chips is refused, and the reference's rebuilt layout (slot ownership,
capacity, slot binding) is the one the program's pool builds.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from aqpbench.tests import sharded_rehearsal  # noqa: E402


def _rehearse(*args) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, str(HERE / "sharded_rehearsal.py"), *args],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _failed(checks, name) -> bool:
    return not checks[name]["value"] <= checks[name]["limit"]


def test_sound_run_is_correct():
    out = _rehearse()
    checks = out["checks"]
    assert out["correct"], checks
    assert checks["checked_answers"]["value"] >= 2
    assert checks["contract_misses"]["value"] == 0


def test_control_is_not_correct():
    out = _rehearse("--control")
    checks = out["checks"]
    assert not out["correct"], checks
    assert _failed(checks, "theta_gap") and _failed(checks, "errbar_gap")


@pytest.mark.parametrize("fault", sharded_rehearsal.FAULTS)
def test_fault_is_caught(fault):
    out = _rehearse("--fault", fault)
    checks = out["checks"]
    assert not out["correct"], checks
    if fault == "state_unchanged":
        assert checks["unanswered"]["value"] > 0
    else:
        assert _failed(checks, "theta_gap")


def test_more_shards_than_chips_is_refused(monkeypatch):
    from aqpbench import run as harness

    config = json.loads(
        (HERE / "tpch-test-returnflag-4shard.json").read_text())
    # Restored after the test: install() replaces it.
    monkeypatch.setattr(harness, "load_json", harness.load_json)
    sharded_rehearsal.install(harness, config={**config, "data_shards": 8})
    with pytest.raises(harness.RunError, match="data_shards=8"):
        harness.run(argparse.Namespace(
            workload=sharded_rehearsal.CELL["name"], seed=1, seconds=1.0,
            trace=0, rehearse=True, rows=1000, control=False))


def test_reference_layout_is_the_pools():
    import jax
    import jax.numpy as jnp

    from aqpbench.reference_sharded import ShardedReference
    from repro.core.sampling import GroupedData, sharded_slot_tables
    from repro.serve.lane_pool import LanePool

    # 4 shards of 1000 rows: a group inside shard 0, one over all four
    # shards, one of 5 rows, and one that ends the table in shard 3.
    offsets = np.array([0, 900, 3100, 3105, 4000])
    values = np.random.default_rng(0).uniform(1, 100, 4000).astype(
        np.float32)
    n_cap, seed = 1024, 77
    # The session's epoch-0 sample key, which the reference rebuilds.
    sample_key = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5A17), 0)
    pool = LanePool(GroupedData(jnp.asarray(values), offsets), lanes=2,
                    n_min=64, n_max=128, n_cap=n_cap, data_shards=4,
                    mesh=False, seed=seed, sample_key=sample_key)
    spec = pool._spec
    ref = ShardedReference(
        values, offsets, session_seed=seed, B=spec["B"], n_min=64,
        n_max=128, n_cap=n_cap, max_iters=spec["max_iters"], l=spec["l"],
        seg_window=spec["seg_window"], data_shards=4)
    layout = pool._layout
    np.testing.assert_array_equal(ref.cap, layout.cap_groups)
    for filled in ([0, 0, 0, 0], [1, 1, 1, 1], [64, 300, 5, 128],
                   [256, 1024, 5, 700], [255, 513, 3, 1000], ref.cap):
        np.testing.assert_array_equal(ref.shard_rows(filled),
                                      layout.shard_rows(filled))
    # The slot binding of sample epoch 0 (the pool's sample key).
    tables = np.asarray(sharded_slot_tables(pool._sample_key, layout,
                                            local_rows=False))
    for g in range(len(offsets) - 1):
        held = ref.sub_size[:, g] > 0
        np.testing.assert_array_equal(ref.tables(0, g)[held],
                                      tables[held, g])


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
