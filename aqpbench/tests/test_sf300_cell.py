"""The four-chip SF 300 cell, rehearsed at a tiny size on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q aqpbench/tests/test_sf300_cell.py

``aqpbench/run.py --rehearse`` runs the cell ``sf300-returnflag-4chip-
solo-closed`` as ``BENCHMARK.json`` and its files declare it, at 300,000
rows, in subprocesses with four host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``): the sound run
reads correct, and ``--control`` (the reference on bfloat16 operands in the
program's place) reads not correct.  In this process: the cell's files, and
the ``psum_ms_per_answer`` reader on synthetic records.
"""
from __future__ import annotations

import ast
import importlib.util
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CELL = "sf300-returnflag-4chip-solo-closed"


def _load(rel: str):
    return json.loads((ROOT / rel).read_text())


@pytest.fixture(scope="module")
def rehearsals() -> dict:
    """stderr of the sound run and of the control, run side by side."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, str(ROOT / "aqpbench" / "run.py"), "--rehearse",
           "--workload", CELL, "--seed", str(2 ** 32 + 301), "--seconds",
           "3", "--rows", "300000"]
    procs = {name: subprocess.Popen(cmd + extra, env=env, cwd=ROOT,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True)
             for name, extra in (("sound", []), ("control", ["--control"]))}
    out = {}
    for name, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=900)
        # A rehearsal ends with code 3 and prints no result line.
        assert proc.returncode == 3 and not stdout.strip(), stderr[-4000:]
        out[name] = stderr
    return out


def _verdict(stderr: str) -> bool:
    found = re.findall(r"rehearsal finished, correct=(True|False)", stderr)
    assert len(found) == 1, stderr[-4000:]
    return found[0] == "True"


def test_sound_run_is_correct(rehearsals):
    err = rehearsals["sound"]
    assert _verdict(err), err[-4000:]
    # Every answer of the window went through the sharded pool.
    routes = re.search(r"routes in the window: (\{.*?\})", err).group(1)
    assert set(ast.literal_eval(routes)) == {"pool"}, routes
    assert "data_shards': 4" in err


def test_control_is_not_correct(rehearsals):
    err = rehearsals["control"]
    assert not _verdict(err), err[-4000:]
    assert re.search(r"check (theta_gap|errbar_gap) = \S+ limit \S+ FAIL",
                     err), err[-4000:]


def test_cell_is_cell_one_on_the_sharded_configuration():
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4
    config = _load(f"aqpbench/configs/{cell['config']}.json")
    assert config["data_shards"] == 4 and config["rows"] == 1_799_989_091
    workload = _load(f"aqpbench/workloads/{CELL}.json")
    cell1 = _load("aqpbench/workloads/sf10-returnflag-solo-closed.json")
    assert workload == {**cell1, "config": cell["config"]}
    psum = next(m for m in bench["per_layer"]
                if m["name"] == "psum_ms_per_answer")
    assert psum["workloads"] == [CELL]


def _psum_reader():
    path = ROOT / "aqpbench" / "metrics" / "psum_ms_per_answer.py"
    spec = importlib.util.spec_from_file_location("psum_ms_per_answer", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record(device_ops, answers=10):
    return {"loop": "closed", "traced_answers": answers,
            "trace": {"device_ops": device_ops, "busy_s": 1.0,
                      "window_s": 3.0, "estimate_kernel_s": 0.1}}


@pytest.mark.parametrize("device_ops, answers, expected", [
    ([["reduce", 0.8], ["all-reduce", 0.05], ["fusion", 0.07]], 10, 5.0),
    ([["reduce", 0.8], ["fusion", 0.07]], 10, None),
    ([["all-reduce-start", 0.01], ["fusion", 0.07], ["all-reduce-done", 0.02],
      ["all-reduce", 0.03], ["my-all-reduce", 0.5]], 20, 3.0),
    ([["all-reduce", 0.05]], 0, None),
])
def test_psum_reader(device_ops, answers, expected):
    got = _psum_reader()(_record(device_ops, answers))
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_psum_reader_without_a_trace():
    assert _psum_reader()({"loop": "closed", "traced_answers": 5}) is None


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
