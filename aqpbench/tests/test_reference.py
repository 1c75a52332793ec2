"""The reference's search for the epoch, the window and the tick.

    JAX_PLATFORMS=cpu python -m pytest -q aqpbench/tests/test_reference.py

Two sample epochs can give one window means that agree far closer than a
wrong epoch as a rule does (cell 2 on seed 4000000043: 3.3e-6 and 2.5e-6 of
theta for a window of 24,193 slots), so theta alone cannot pick the epoch.
Here epoch 1 binds the window's slots to the rows of epoch 0 in reverse
order: theta is the same in both, and only the error bar, whose replicate
weights follow the slot, tells them apart.

A group with fewer rows than the init design's stacked windows reach (as in
a CPU rehearsal at 100,000 rows) still has ``n_cap`` slots, bound to its
rows with repeats: the reference follows a window past its rows.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from aqpbench import reference as R  # noqa: E402

N_WIN, TICK = 3000, 7
TRAJ = dict(B=64, n_min=100, n_max=200, n_cap=4096, max_iters=12, l=4,
            ext_cap=1024)


class MirroredEpochs(R.Reference):
    """Epoch 1 reads epoch 0's rows of slots ``[0, N_WIN)`` backwards."""

    def rows(self, epoch, grouped, g):
        r = super().rows(0, grouped, g)
        if epoch == 0:
            return r
        return np.concatenate([r[:N_WIN][::-1], r[N_WIN:]])


def _served(ref, epoch, key):
    """A grouped SUM lane's answer for group 1, drawn from ``epoch`` at
    ``TICK`` over the window ``[0, N_WIN)``, with theta as the window of
    epoch 0 gives it."""
    scale = np.array([ref.scale_of("sum", 1)])
    x = ref.values[ref.rows(epoch, True, 1)[:N_WIN]]
    base = R.key_bits(key, R.SALT_BOOT)
    seed = ref.seeds([base], TICK, True, [1])[0][0]
    th, reps = R.estimate([[(x, np.arange(N_WIN, dtype=np.uint32), seed)]],
                          "sum", ref.B)
    theta = np.array([ref._theta(
        ref.values[ref.rows(0, True, 1)[:N_WIN]].astype(np.float64), "sum",
        scale[0])])
    return theta, R.error_bar(reps, th, scale, 0.05)


@pytest.mark.parametrize("epoch", [0, 1])
def test_error_bar_picks_among_epochs_theta_cannot_tell_apart(epoch):
    rng = np.random.default_rng(7)
    values = rng.gamma(2.0, 2e4, 120_000).astype(np.float32)
    offsets = np.array([0, 50_000, 120_000])
    ref = MirroredEpochs(values, offsets, session_seed=12345, **TRAJ)
    key = np.array([0x1234, 0xABCD], np.uint32)
    theta, error = _served(ref, epoch, key)
    s = R.Served(func="sum", delta=0.05, key=key, theta=theta,
                 error=np.array([error]), n=np.array([N_WIN]), group_by=True)
    th_gap, eb_gap, _, _, got_epoch, got_tick = ref.check_lane(
        s, 1, [1], [key], theta, error, np.array([N_WIN]), grouped=True)
    assert (got_epoch, got_tick) == (epoch, TICK)
    assert th_gap < 1e-12 and eb_gap < 1e-9


def test_init_window_past_a_small_groups_rows():
    rng = np.random.default_rng(11)
    values = rng.gamma(2.0, 2e4, 60_000).astype(np.float32)
    offsets = np.array([0, 50_000, 50_400, 60_000])    # group 1: 400 rows
    ref = R.Reference(values, offsets, session_seed=999, **TRAJ)
    key = np.array([0x5151, 0x7777], np.uint32)
    # Ticks 0-2 read n_min slots each, tick 3 n_max: slots [300, 500).
    lo, hi, tick = 300, 500, 3
    assert ref.window(tick, np.array([hi - lo]), [1]) is not None
    seed = R.key_bits(R.fold_in(R.fold_in(ref._root, 0), 1), R.SALT_SLOT)
    rows = R.slot_rows(seed, 0, 50_000, 400, TRAJ["n_cap"])[lo:hi]
    x = values[rows]
    scale = np.array([ref.scale_of("avg", 1)])
    boot = ref.seeds([R.key_bits(key, R.SALT_BOOT)], tick, True, [1])[0][0]
    th, reps = R.estimate([[(x, np.arange(lo, hi, dtype=np.uint32), boot)]],
                          "avg", ref.B)
    error = R.error_bar(reps, th, scale, 0.05)
    s = R.Served(func="avg", delta=0.05, key=key, theta=th,
                 error=np.array([error]), n=np.array([hi - lo]),
                 group_by=True)
    th_gap, eb_gap, _, _, epoch, got_tick = ref.check_lane(
        s, 0, [1], [key], th, error, np.array([hi - lo]), grouped=True)
    assert (epoch, got_tick) == (0, tick)
    assert th_gap < 1e-12 and eb_gap < 1e-9
