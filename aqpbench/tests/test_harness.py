"""The harness's verdict on sound and on broken serving, at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest -q aqpbench/tests/test_harness.py

Each test drives a whole run of a cell on the CPU (``--rehearse``: the look
for a chip is skipped, everything else runs, the chip's Pallas kernels
interpreted) over a table of a few hundred thousand rows, for both cells:

* the sound program reads ``correct``; with ``--control`` (the reference on
  bfloat16 operands put in the program's place) the run reads not correct,
  and the control fails both recomputation limits;
* with the timed path broken underneath, ``correct`` comes out false for
  each fault a one-chip serving cell can have: a step that returns its
  state unchanged, half of each lane's sample left out of the estimate,
  and an answer altered where the step produces it.  (These cells hold no
  exchange between chips; ``test_sharded.py`` plants that fault, and the
  others, in a row-sharded cell on four host devices.)
* with the answer contract weakened inside the program, ``correct`` comes
  out false: lanes that stop after two ticks (at the init design's n_min
  and n_max), and a stopping test run at twice the requested epsilon.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from aqpbench import run as harness  # noqa: E402

CELLS = {"sf10-returnflag-solo-closed": 200_000,
         "sf10-linenumber-groupby-closed": 400_000}


def _run(monkeypatch, cell, control=False):
    monkeypatch.setattr(harness, "DRAIN_GRACE_S", 30.0)
    monkeypatch.setattr(harness, "WARMUP_LIMIT_S", 20.0)
    # The CPU recomputes an answer several times slower than the chip.
    monkeypatch.setattr(harness, "SAMPLE_SOLO", 8)
    monkeypatch.setattr(harness, "SAMPLE_GROUPED", 3)
    jax.clear_caches()
    args = argparse.Namespace(
        workload=cell, seed=2 ** 33 + 17, seconds=3.0, trace=0,
        rehearse=True, rows=CELLS[cell], control=control)
    result, rehearsal = harness.run(args)
    jax.clear_caches()
    assert rehearsal
    return result


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct_and_control_is_not(monkeypatch, cell):
    result = _run(monkeypatch, cell)
    checks = result["checks"]
    assert result["correct"], checks
    assert checks["checked_answers"]["value"] >= 2
    assert checks["contract_misses"]["value"] == 0
    result = _run(monkeypatch, cell, control=True)
    checks = result["checks"]
    assert not result["correct"], checks
    gaps = [k for k in checks if "_gap" in k]
    assert any(k.startswith("theta_gap") for k in gaps)
    assert any(k.startswith("errbar_gap") for k in gaps)
    for k in gaps:
        assert checks[k]["value"] > checks[k]["limit"], k


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_state_left_unchanged_is_caught(monkeypatch, cell):
    from repro.serve import lane_pool

    real = lane_pool.fused_step

    class Frozen:
        def __call__(self, values, offsets, state, params, *a, **kw):
            return state

        def _cache_size(self):
            return real._cache_size()

    monkeypatch.setattr(lane_pool, "fused_step", Frozen())
    result = _run(monkeypatch, cell)
    assert not result["correct"]
    assert result["checks"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_half_the_sample_left_out_is_caught(monkeypatch, cell):
    from repro.core import bootstrap

    lanes, segments = bootstrap.lane_moment_sums, bootstrap.segment_moment_sums

    def half_lanes(v, mf, seeds, B, **kw):
        # Keep the first half of each lane's window: the estimate is the
        # mean over the rest while the lane still reports all n rows.
        count = jnp.sum(mf, axis=-1, keepdims=True)
        keep = jnp.cumsum(mf, axis=-1) <= jnp.ceil(count / 2)
        return lanes(v, mf * keep, seeds, B, **kw)

    def half_segments(x, gid, slot, valid, seeds, q, B, **kw):
        # The grouped path's packed stream: drop every odd slot.
        return segments(x, gid, slot, valid * (slot % 2 == 0), seeds, q, B,
                        **kw)

    monkeypatch.setattr(bootstrap, "lane_moment_sums", half_lanes)
    monkeypatch.setattr(bootstrap, "segment_moment_sums", half_segments)
    result = _run(monkeypatch, cell)
    assert not result["correct"]
    assert not result["checks"]["theta_gap"]["value"] \
        <= result["checks"]["theta_gap"]["limit"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_answer_altered_where_produced_is_caught(monkeypatch, cell):
    from repro.core import fused

    real = fused._lane_epilogue

    def altered(s, p, **kw):
        kw["theta_b"] = kw["theta_b"] * (1.0 + 1e-4)
        return real(s, p, **kw)

    monkeypatch.setattr(fused, "_lane_epilogue", altered)
    result = _run(monkeypatch, cell)
    assert not result["correct"]
    assert not result["checks"]["theta_gap"]["value"] \
        <= result["checks"]["theta_gap"]["limit"]


@pytest.mark.parametrize("fault", ["lanes_cut_short", "epsilon_doubled"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_contract_weakened_in_the_program_is_caught(monkeypatch, cell,
                                                     fault):
    import repro.serve as serve

    real = serve.AQPSession

    class Weakened(real):
        if fault == "lanes_cut_short":
            def __init__(self, data, **kw):
                super().__init__(data, **{**kw, "max_iters": 2})
        else:
            def submit(self, request, key=None):
                q = request.query
                q = dataclasses.replace(q, epsilon=2.0 * q.epsilon)
                return super().submit(
                    dataclasses.replace(request, query=q), key=key)

    monkeypatch.setattr(serve, "AQPSession", Weakened)
    result = _run(monkeypatch, cell)
    checks = result["checks"]
    assert not result["correct"], checks
    assert checks["contract_misses"]["value"] > 0


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
