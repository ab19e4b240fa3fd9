"""Packed direction fields + parallel assignment unit tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_distributed_tswap_tpu.core.agent import AgentPhase
from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.core.grid import Grid
from p2p_distributed_tswap_tpu.ops.distance import (
    DIR_STAY,
    direction_fields,
    gather_packed,
    pack_directions,
    packed_cells,
)
from p2p_distributed_tswap_tpu.solver import mapd
from p2p_distributed_tswap_tpu.solver.mapd import (
    _assign,
    init_state,
    solve_offline,
)


def test_pack_gather_roundtrip_even_and_odd():
    rng = np.random.default_rng(0)
    for hw in (10, 11, 64, 101):
        fields = rng.integers(0, 5, size=(3, hw)).astype(np.uint8)
        packed = pack_directions(jnp.asarray(fields))
        assert packed.shape == (3, packed_cells(hw))
        rows = jnp.asarray(np.repeat(np.arange(3), hw).astype(np.int32))
        pos = jnp.asarray(np.tile(np.arange(hw), 3).astype(np.int32))
        got = np.asarray(gather_packed(packed, rows, pos)).reshape(3, hw)
        np.testing.assert_array_equal(got, fields)


def test_pack_odd_tail_is_stay():
    fields = jnp.zeros((1, 5), jnp.uint8)  # cell count not a lane multiple
    packed = pack_directions(fields)
    # nibbles 5..7 of the last word are the DIR_STAY pad
    word = int(packed[0, -1])
    for lane in range(5, 8):
        assert (word >> (4 * lane)) & 0xF == DIR_STAY


def test_packed_fields_match_unpacked_semantics():
    grid = Grid.random_obstacles(12, 12, 0.2, seed=4)
    goals = jnp.asarray([5, 17, 100], jnp.int32)
    fields = direction_fields(jnp.asarray(grid.free), goals).reshape(3, -1)
    packed = pack_directions(fields)
    pos = jnp.asarray(np.arange(grid.num_cells, dtype=np.int32))
    for r in range(3):
        rows = jnp.full(grid.num_cells, r, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(gather_packed(packed, rows, pos)),
            np.asarray(fields[r]))


def _np_parallel_assign(pos, phase, task_used, tasks, w):
    """Literal numpy model of the round-based parallel assignment."""
    n, t = len(pos), len(tasks)
    task_used = task_used.copy()
    goal = np.full(n, -1, np.int64)
    agent_task = np.full(n, -1, np.int64)
    phase = phase.copy()
    while True:
        proposals = {}
        for i in range(n):
            if phase[i] != AgentPhase.IDLE or agent_task[i] >= 0:
                continue
            best, bk = None, -1
            for k in range(t):
                if task_used[k]:
                    continue
                d = (abs(tasks[k, 0] % w - pos[i] % w)
                     + abs(tasks[k, 0] // w - pos[i] // w))
                if best is None or d < best:
                    best, bk = d, k
            if bk >= 0:
                proposals.setdefault(bk, []).append(i)
        if not proposals:
            return goal, agent_task, task_used
        for k, claimants in proposals.items():
            i = min(claimants)
            task_used[k] = True
            goal[i] = tasks[k, 0]
            agent_task[i] = k
            phase[i] = AgentPhase.TO_PICKUP


def _tied_pairs(rng, cells, t):
    """Pickups in identical pairs (1, 2), (3, 4), ...: with a task chunk
    of 2 every pair straddles a chunk boundary, and each pair's lower
    index must win its ties."""
    pick = rng.choice(cells, size=t // 2 + 1, replace=False)
    tasks = rng.choice(cells, size=(t, 2)).astype(np.int32)
    tasks[:, 0] = pick[(np.arange(t) + 1) // 2]
    return tasks


# (side, agents, tasks, assign_chunk, share of agents not idle, share of
# tasks already used, pickups tied across chunk boundaries)
ASSIGN_CASES = {
    # a fleet no wider than one bid pass: every row bids at once
    "one-pass-fleet": (16, 9, 7, 3, 0.0, 0.0, False),
    # 300 idle agents: three passes of 128 in the first round
    "several-passes": (32, 300, 200, 1024, 0.0, 0.0, False),
    # only some agents idle, part of the backlog already taken
    "mixed-phases": (32, 300, 400, 1024, 0.6, 0.3, False),
    # task chunks of 2: each tied pair straddles a chunk boundary
    "ties-across-chunks": (24, 256, 300, 2, 0.0, 0.0, True),
    # fewer unused orders than idle agents: the backlog runs dry
    "backlog-runs-dry": (32, 300, 150, 1024, 0.2, 0.2, False),
}


@pytest.mark.parametrize("case", list(ASSIGN_CASES))
def test_parallel_assignment_matches_round_model(case):
    side, n, t, chunk, busy, taken, tied = ASSIGN_CASES[case]
    rng = np.random.default_rng(1)
    grid = Grid.from_ascii("\n".join(["." * side] * side))
    pos = rng.choice(grid.num_cells, size=n, replace=False).astype(np.int32)
    tasks = (_tied_pairs(rng, grid.num_cells, t) if tied
             else rng.choice(grid.num_cells, size=(t, 2)).astype(np.int32))
    phase = np.where(rng.random(n) < busy, AgentPhase.TO_DELIVERY,
                     AgentPhase.IDLE).astype(np.int8)
    used0 = rng.random(t) < taken
    cfg = SolverConfig(height=side, width=side, num_agents=n,
                       assign_chunk=chunk)
    s = init_state(cfg, jnp.asarray(pos), t).replace(
        phase=jnp.asarray(phase), task_used=jnp.asarray(used0))
    out = _assign(cfg, s, jnp.asarray(tasks))
    g_np, at_np, used_np = _np_parallel_assign(
        pos, np.asarray(s.phase), used0, tasks, side)
    assigned = at_np >= 0
    np.testing.assert_array_equal(np.asarray(out.agent_task), at_np)
    np.testing.assert_array_equal(np.asarray(out.task_used), used_np)
    np.testing.assert_array_equal(
        np.asarray(out.goal)[assigned], g_np[assigned])
    # unassigned agents keep their previous (start) goal
    np.testing.assert_array_equal(
        np.asarray(out.goal)[~assigned], pos[~assigned])
    idle = int((phase == AgentPhase.IDLE).sum())
    assert used_np.sum() == used0.sum() + min(idle, t - used0.sum())
    np.testing.assert_array_equal(
        np.asarray(out.phase),
        np.where(assigned, AgentPhase.TO_PICKUP, phase))


def test_compacted_bids_solve_as_full_width(monkeypatch):
    """A warehouse fleet wider than one bid pass solves to the same paths
    whether its rounds bid the idle agents in passes or every row at once
    (the pass width patched up to the fleet)."""
    from p2p_distributed_tswap_tpu.core.sampling import start_positions_array
    from p2p_distributed_tswap_tpu.core.tasks import TaskGenerator

    # 2x8 shelf blocks between 2-cell aisles
    shelves = "." + ("@" * 8 + "..") * 3 + "." * 9
    grid = Grid.from_ascii("\n".join(
        shelves if y % 4 in (2, 3) else "." * 40 for y in range(20)))
    n = 160
    assert n > mapd._BID_LANES
    starts = start_positions_array(grid, n, seed=5)
    tasks = TaskGenerator(grid, seed=6).generate_task_arrays(2 * n)
    cfg = SolverConfig(height=20, width=40, num_agents=n, replan_chunk=32,
                       max_timesteps=300)

    def solve():
        jax.clear_caches()   # the pass width is read while tracing
        return solve_offline(grid, starts, tasks, cfg=cfg)

    try:
        paths, states, makespan = solve()
        monkeypatch.setattr(mapd, "_BID_LANES", n)
        full_paths, full_states, full_makespan = solve()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert 0 < makespan <= cfg.max_timesteps
    assert makespan == full_makespan
    np.testing.assert_array_equal(paths, full_paths)
    np.testing.assert_array_equal(states, full_states)


def test_record_paths_off_solves_identically():
    from p2p_distributed_tswap_tpu.core.sampling import start_positions_array
    from p2p_distributed_tswap_tpu.core.tasks import TaskGenerator

    grid = Grid.random_obstacles(14, 14, 0.15, seed=2)
    starts = start_positions_array(grid, 4, seed=3)
    tasks = TaskGenerator(grid, seed=4).generate_task_arrays(3)
    cfg_on = SolverConfig(height=14, width=14, num_agents=4)
    cfg_off = SolverConfig(height=14, width=14, num_agents=4,
                           record_paths=False)
    p_on, s_on, mk_on = solve_offline(grid, starts, tasks, cfg=cfg_on)
    p_off, s_off, mk_off = solve_offline(grid, starts, tasks, cfg=cfg_off)
    assert mk_on == mk_off
    assert p_off.shape == (0, 4) and s_off.shape == (0, 4)
    assert p_on.shape == (mk_on, 4)
