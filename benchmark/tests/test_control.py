"""``correct`` comes out false for the control and for every fault a
cell can have, with the rest of the run driven as usual (tiny sizes,
chip check stubbed).  The faults: a step that returns its state
unchanged, half of the fleet left out of a step, a planned position
altered where it is produced.  (A one-chip cell has no exchange between
chips to leave out.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_cell


def _start(tree, capsys, *extra):
    return run_cell(tree, capsys, "--workload", "tiny.ticks", "--seed", "5",
                    "--seconds", "2", "--trace", "0", *extra)


def _batch(tree, capsys, *extra):
    return run_cell(tree, capsys, "--workload", "tiny.batch", "--seed", "5",
                    "--seconds", "2", "--trace", "0", *extra)


def test_sound_runs_pass(tree, capsys):
    assert _start(tree, capsys)["correct"]
    assert _batch(tree, capsys)["correct"]


@pytest.mark.parametrize("cell", ["tiny.ticks", "tiny.batch"])
def test_control_fails(tree, capsys, cell):
    line = run_cell(tree, capsys, "--workload", cell, "--seed", "5",
                    "--seconds", "2", "--trace", "0", "--control")
    assert not line["correct"], line["checks"]


def _ticks_driver(tree):
    from harness import spec
    return spec.driver("offline_ticks", tree / "benchmark")


def _solves_driver(tree):
    from harness import spec
    return spec.driver("offline_solves", tree / "benchmark")


def test_start_state_unchanged_fails(tree, capsys, monkeypatch):
    prog = _ticks_driver(tree).Program

    def tick(self, s):
        return s, jax.device_get((s.pos, s.goal, s.phase))

    monkeypatch.setattr(prog, "tick", tick)
    line = _start(tree, capsys)
    assert not line["correct"] and line["checks"]["plan_mismatch"]["value"]


def test_start_half_fleet_left_out_fails(tree, capsys, monkeypatch):
    prog = _ticks_driver(tree).Program
    real = prog.tick

    def tick(self, s):
        old = jnp.array(s.pos, copy=True)
        s, _ = real(self, s)
        half = jnp.arange(old.shape[0]) % 2 == 1
        s = s.replace(pos=jnp.where(half, old, s.pos))
        return s, jax.device_get((s.pos, s.goal, s.phase))

    monkeypatch.setattr(prog, "tick", tick)
    assert not _start(tree, capsys)["correct"]


def test_start_altered_position_fails(tree, capsys, monkeypatch):
    prog = _ticks_driver(tree).Program
    real = prog.tick

    def tick(self, s):
        s, (pos, goal, phase) = real(self, s)
        pos = pos.copy()
        pos[7] = pos[8]             # two robots on one cell
        return s, (pos, goal, phase)

    monkeypatch.setattr(prog, "tick", tick)
    line = _start(tree, capsys)
    assert not line["correct"] and line["checks"]["illegal_moves"]["value"]


def test_batch_state_unchanged_fails(tree, capsys, monkeypatch):
    prog = _solves_driver(tree).Program
    real = prog.solve

    def solve(self, b):
        t, pp, ps = real(self, b)
        return t, np.repeat(pp[:1], t, axis=0), ps

    monkeypatch.setattr(prog, "solve", solve)
    assert not _batch(tree, capsys)["correct"]


def test_batch_half_fleet_left_out_fails(tree, capsys, monkeypatch):
    prog = _solves_driver(tree).Program
    real = prog.solve

    def solve(self, b):
        t, pp, ps = real(self, b)
        pp = pp.copy()
        starts = np.asarray(self.batches[b][0])
        pp[:, 1::2] = starts[1::2]
        return t, pp, ps

    monkeypatch.setattr(prog, "solve", solve)
    assert not _batch(tree, capsys)["correct"]


def test_batch_altered_position_fails(tree, capsys, monkeypatch):
    prog = _solves_driver(tree).Program
    real = prog.solve

    def solve(self, b):
        t, pp, ps = real(self, b)
        pp = pp.copy()
        pp[t // 2, 0] = pp[t // 2, 1]   # two robots on one cell
        return t, pp, ps

    monkeypatch.setattr(prog, "solve", solve)
    assert not _batch(tree, capsys)["correct"]
