"""ops/field_fused.py — the fused direction-field kernels.

Interpreter-mode bit-identity against the portable pipeline
(distance_fields + directions_from_distance) on adversarial inputs:
random obstacles, unreachable pockets, goal on an obstacle, goal in a
corner — for BOTH the round-3 single-field kernel (on-chip bit-identity
at 256^2/1024^2 was verified in round 3) and the ISSUE 9 multi-field
kernel (8 fields per program across sublanes; no TPU in this
environment, so interpreter identity is the gate until an on-chip run).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from p2p_distributed_tswap_tpu.ops import distance, field_fused


@pytest.fixture(autouse=True)
def _interpret_mode():
    field_fused.INTERPRET = True
    yield
    field_fused.INTERPRET = False


def _reference(free, goals):
    return np.asarray(distance.directions_from_distance(
        distance.distance_fields(free, goals), free))


def _fused(free, goals):
    return np.asarray(field_fused.single_direction_fields(free, goals))


def test_random_obstacles_bit_identical():
    rng = np.random.default_rng(0)
    free_np = rng.random((128, 128)) > 0.3  # dense walls: pockets exist
    free = jnp.asarray(free_np)
    cells = np.flatnonzero(free_np.reshape(-1))
    goals = jnp.asarray(rng.choice(cells, 3), jnp.int32)
    np.testing.assert_array_equal(_reference(free, goals),
                                  _fused(free, goals))


def test_goal_on_obstacle_and_corner():
    rng = np.random.default_rng(1)
    free_np = rng.random((64, 128)) > 0.2
    free_np[0, 0] = True       # corner goal
    free_np[5, 7] = False      # obstacle goal
    free = jnp.asarray(free_np)
    goals = jnp.asarray([0, 5 * 128 + 7, 63 * 128 + 127], jnp.int32)
    np.testing.assert_array_equal(_reference(free, goals),
                                  _fused(free, goals))


def test_empty_grid_single_goal():
    free = jnp.ones((8, 128), bool)
    goals = jnp.asarray([3 * 128 + 64], jnp.int32)
    np.testing.assert_array_equal(_reference(free, goals),
                                  _fused(free, goals))


# -- multi-field kernel (ISSUE 9: 8 fields/program across sublanes) -------


def _multi(free, goals):
    return np.asarray(field_fused.multi_direction_fields(free, goals))


def test_multi_random_obstacles_bit_identical():
    """Full 8-field program plus a second program (G=16)."""
    rng = np.random.default_rng(2)
    free_np = rng.random((64, 128)) > 0.3
    free = jnp.asarray(free_np)
    cells = np.flatnonzero(free_np.reshape(-1))
    goals = jnp.asarray(rng.choice(cells, 16, replace=False), jnp.int32)
    np.testing.assert_array_equal(_reference(free, goals),
                                  _multi(free, goals))


def test_multi_ragged_batch_pads_with_last_goal():
    """G=11 (not a multiple of 8): padded fields are computed and
    dropped; the visible batch stays bit-identical."""
    rng = np.random.default_rng(3)
    free_np = rng.random((32, 128)) > 0.25
    free = jnp.asarray(free_np)
    cells = np.flatnonzero(free_np.reshape(-1))
    goals = jnp.asarray(rng.choice(cells, 11, replace=False), jnp.int32)
    out = _multi(free, goals)
    assert out.shape == (11, 32, 128)
    np.testing.assert_array_equal(_reference(free, goals), out)


def test_multi_goal_on_obstacle_and_corner():
    rng = np.random.default_rng(4)
    free_np = rng.random((16, 128)) > 0.2
    free_np[0, 0] = True
    free_np[5, 7] = False
    free = jnp.asarray(free_np)
    goals = jnp.asarray([0, 5 * 128 + 7, 15 * 128 + 127] * 3, jnp.int32)
    np.testing.assert_array_equal(_reference(free, goals),
                                  _multi(free, goals))


def test_multi_eligibility_and_mode(monkeypatch):
    # shape gate: lane-aligned + 8-row-aligned + VMEM budget
    assert field_fused.multi_eligible(64, 128)
    assert not field_fused.multi_eligible(60, 128)   # H % 8
    assert not field_fused.multi_eligible(64, 100)   # W % 128
    assert not field_fused.multi_eligible(1024, 1024)  # VMEM budget
    # env mode selection (backend-gated dispatch itself needs a TPU)
    monkeypatch.delenv("MAPD_FUSED", raising=False)
    assert field_fused.fused_mode() == ""
    monkeypatch.setenv("MAPD_FUSED", "1")
    assert field_fused.fused_mode() == "multi"
    monkeypatch.setenv("MAPD_FUSED", "multi")
    assert field_fused.fused_mode() == "multi"
    monkeypatch.setenv("MAPD_FUSED", "single")
    assert field_fused.fused_mode() == "single"
    # CPU backend: never eligible no matter the env (the gate goes
    # through _on_tpu)
    assert not field_fused.fused_eligible(64, 128)
