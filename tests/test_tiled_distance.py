"""Grid-tile-sharded sweeps (ops/tiled_distance.py): the H-banded,
halo-exchanged fields must be BIT-IDENTICAL to the single-device sweep —
the correctness contract that makes spatial decomposition (SURVEY §7 step 6,
the reference's geographic-partitioning proposal) a pure memory/scale
optimization."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from p2p_distributed_tswap_tpu.core.grid import Grid
from p2p_distributed_tswap_tpu.ops.distance import (
    direction_fields,
    distance_fields,
)
from p2p_distributed_tswap_tpu.ops.tiled_distance import (
    TILES_AXIS,
    tiled_direction_fields,
    tiled_distance_fields,
)

N_DEV = 8


def _mesh():
    return Mesh(np.array(jax.devices("cpu")[:N_DEV]), (TILES_AXIS,))


def _run_tiled(fn, grid, goals):
    """Shard the grid's H axis over the mesh and run a tiled op inside
    shard_map; returns the reassembled global result."""
    free = jnp.asarray(grid.free)
    goals = jnp.asarray(goals, jnp.int32)
    mesh = _mesh()
    tiled = jax.jit(jax.shard_map(
        functools.partial(fn, width=grid.width),
        mesh=mesh,
        in_specs=(P(TILES_AXIS, None), P()),
        out_specs=P(None, TILES_AXIS, None),
        check_vma=False))
    return np.asarray(tiled(free, goals))


GRIDS = [
    ("warehouse", Grid.warehouse(64, 64)),
    ("obstacles", Grid.random_obstacles(64, 64, 0.25, seed=3)),
    # vertical wall with one slit at the bottom: shortest paths between the
    # halves must snake through many bands -> exercises multi-round halo
    # propagation (information crosses one band boundary per round)
    ("slit", Grid.from_ascii("\n".join(
        ["." * 31 + "@" + "." * 32] * 63 + ["." * 64]))),
]


@pytest.mark.parametrize("name,grid", GRIDS, ids=[g[0] for g in GRIDS])
def test_tiled_distance_matches_single_device(name, grid):
    rng = np.random.default_rng(7)
    free_cells = np.flatnonzero(np.asarray(grid.free).reshape(-1))
    goals = rng.choice(free_cells, size=5, replace=False).astype(np.int32)
    want = np.asarray(distance_fields(jnp.asarray(grid.free),
                                      jnp.asarray(goals)))
    got = _run_tiled(tiled_distance_fields, grid, goals)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name,grid", GRIDS, ids=[g[0] for g in GRIDS])
def test_tiled_directions_match_single_device(name, grid):
    rng = np.random.default_rng(11)
    free_cells = np.flatnonzero(np.asarray(grid.free).reshape(-1))
    goals = rng.choice(free_cells, size=4, replace=False).astype(np.int32)
    want = np.asarray(direction_fields(jnp.asarray(grid.free),
                                       jnp.asarray(goals)))
    got = _run_tiled(tiled_direction_fields, grid, goals)
    np.testing.assert_array_equal(got, want)


def test_tiled_unreachable_and_obstacle_goal():
    # goal on an obstacle -> all-INF band everywhere; sealed room -> INF
    grid = Grid.from_ascii("\n".join(
        ["." * 16] * 6
        + ["@" * 16]          # full wall seals the bottom off
        + ["." * 16] * 9))
    goal_open = grid.idx((2, 2))
    goal_sealed = grid.idx((2, 10))
    want = np.asarray(distance_fields(
        jnp.asarray(grid.free),
        jnp.asarray([goal_open, goal_sealed], jnp.int32)))
    got = _run_tiled(tiled_distance_fields, grid,
                     np.asarray([goal_open, goal_sealed], np.int32))
    np.testing.assert_array_equal(got, want)
