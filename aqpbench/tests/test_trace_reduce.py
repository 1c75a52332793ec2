"""The trace reduction on a hand-made trace and on a recorded chip trace.

    python -m pytest -q aqpbench/tests/test_trace_reduce.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from aqpbench.trace_reduce import op_name, reduce  # noqa: E402

SAMPLE = Path(__file__).with_name("trace_sample.json")


def test_hand_made_trace():
    ex = {"host": [["pump", 0.0, 100.0], ["wait", 100.0, 200.0]],
          "device": {"/device:TPU:0": [
              ["fusion", 10.0, 30.0], ["copy", 20.0, 50.0],
              ["while", 60.0, 90.0],
              ["poisson_bootstrap_moments_lanes", 70.0, 80.0]]}}
    out = reduce(ex)
    assert out["window_s"] == pytest.approx(200e-9)
    assert out["busy_s"] == pytest.approx(70e-9)        # [10,50] + [60,90]
    assert out["estimate_kernel_s"] == pytest.approx(10e-9)
    ops = dict(out["device_ops"])
    assert ops["while"] == pytest.approx(20e-9)         # 30 less its child
    assert ops["poisson_bootstrap_moments_lanes"] == pytest.approx(10e-9)
    assert out["idle_by_label"] == pytest.approx(
        {"pump": 30e-9, "wait": 100e-9})
    assert out["idle_gaps"][0] == ["wait", pytest.approx(100e-9)]


def test_recorded_chip_trace():
    ex = json.loads(SAMPLE.read_text())
    out = reduce(ex)
    w0 = min(a for _, a, _ in ex["host"])
    w1 = max(b for _, _, b in ex["host"])
    # Busy time by brute force on a 10 ns grid.
    grid = np.zeros(int((w1 - w0) // 10) + 1, bool)
    kernel = 0.0
    for name, a, b in ex["device"]["/device:TPU:0"]:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            grid[int((a - w0) // 10):int((b - w0) // 10)] = True
            if "poisson_bootstrap" in name:
                kernel += (b - a) * 1e-9
    assert out["busy_s"] == pytest.approx(grid.sum() * 10e-9, rel=1e-3)
    assert out["estimate_kernel_s"] == pytest.approx(kernel)
    assert kernel > 0
    idle = sum(out["idle_by_label"].values())
    assert idle + out["busy_s"] == pytest.approx(out["window_s"], rel=1e-9)
    assert sum(v for _, v in out["device_ops"]) <= out["busy_s"] * (1 + 1e-9)


def test_op_names():
    assert op_name("%poisson_bootstrap_moments_lanes.9 = f32[12,8,512] "
                   "custom-call(u32[12] %a)") == \
        "poisson_bootstrap_moments_lanes"
    assert op_name("%while.1 = (s32[]) while(%t)") == "while"
    assert op_name("copy-start") == "copy-start"


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
