"""``correct`` comes out false for the decentralized cell's control
(goal exchanges committed at once, ``swap_commit_delay`` 0) and for
every fault a cell can have: a step that returns its state unchanged,
half of the fleet left out of a step, a planned position altered where
it is produced (tiny sizes, chip check stubbed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stale_cell import CELL, add_cell, driver, run_decent


@pytest.fixture
def decent(tree):
    return add_cell(tree)


def test_control_fails(decent, capsys):
    line = run_decent(decent, capsys, "--control")
    assert not line["correct"], line["checks"]
    assert line["checks"]["plan_mismatch"]["value"] > 0


def test_state_unchanged_fails(decent, capsys, monkeypatch):
    def tick(self, s):
        return s, jax.device_get((s.pos, s.goal, s.phase))

    monkeypatch.setattr(driver(decent).Program, "tick", tick)
    line = run_decent(decent, capsys)
    assert not line["correct"] and line["checks"]["plan_mismatch"]["value"]


def test_half_fleet_left_out_fails(decent, capsys, monkeypatch):
    prog = driver(decent).Program
    real = prog.tick

    def tick(self, s):
        old = jnp.array(s.pos, copy=True)
        s, _ = real(self, s)
        half = jnp.arange(old.shape[0]) % 2 == 1
        s = s.replace(pos=jnp.where(half, old, s.pos))
        return s, jax.device_get((s.pos, s.goal, s.phase))

    monkeypatch.setattr(prog, "tick", tick)
    assert not run_decent(decent, capsys)["correct"]


def test_altered_position_fails(decent, capsys, monkeypatch):
    prog = driver(decent).Program
    real = prog.tick

    def tick(self, s):
        s, (pos, goal, phase) = real(self, s)
        pos = np.array(pos)
        pos[7] = pos[8]             # two robots on one cell
        return s, (pos, goal, phase)

    monkeypatch.setattr(prog, "tick", tick)
    line = run_decent(decent, capsys)
    assert not line["correct"] and line["checks"]["illegal_moves"]["value"]


def test_later_replay_that_differs_fails(decent, capsys, monkeypatch):
    """Replays after the first are compared with it as they come: one
    that differs is caught, though its observations are not kept."""
    prog = driver(decent).Program
    real = prog.tick
    seg = CELL["segment"]
    calls = []

    def tick(self, s):
        s, (pos, goal, phase) = real(self, s)
        calls.append(1)
        if len(calls) > seg["start"] + seg["ticks"]:
            goal = np.array(goal)
            goal[3] += 1
        return s, (pos, goal, phase)

    monkeypatch.setattr(prog, "tick", tick)
    line = run_decent(decent, capsys)
    assert not line["correct"] and line["checks"]["replay_diff"]["value"]
    assert line["checks"]["plan_mismatch"]["value"] == 0
