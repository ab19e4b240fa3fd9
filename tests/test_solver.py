"""Solver tests: the batched parallel TSWAP solve vs the sequential oracle.

The oracle (solver/oracle.py) is the transcribed sequential semantics of the
reference's tswap_mapd; the parallel solver must hold the hard invariants
(vertex-disjointness, legal unit moves, obstacle avoidance, completion) and
stay within a modest makespan factor of the oracle (SURVEY §7 hard part 1:
orderings differ, parity is empirical).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from p2p_distributed_tswap_tpu.core.agent import AgentPhase
from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.core.grid import Grid
from p2p_distributed_tswap_tpu.core.sampling import start_positions_array
from p2p_distributed_tswap_tpu.core.tasks import TaskGenerator
from p2p_distributed_tswap_tpu.solver.mapd import run_mapd, solve_offline
from p2p_distributed_tswap_tpu.solver.oracle import OracleSim


def _scenario(grid, n_agents, n_tasks, seed):
    starts = start_positions_array(grid, n_agents, seed=seed)
    tasks = TaskGenerator(grid, seed=seed + 1).generate_task_arrays(n_tasks)
    return starts, tasks


def _check_paths(grid, paths_pos):
    """Hard invariants on a (T, N) position history."""
    t_len, n = paths_pos.shape
    w = grid.width
    free_flat = grid.free.reshape(-1)
    for t in range(t_len):
        row = paths_pos[t]
        assert len(np.unique(row)) == n, f"vertex collision at t={t}"
        assert free_flat[row].all(), f"agent on obstacle at t={t}"
        if t > 0:
            # per-axis unit moves only (a bare flat-delta check would accept
            # row-wraparound steps like (y, w-1) -> (y+1, 0))
            dx = row % w - paths_pos[t - 1] % w
            dy = row // w - paths_pos[t - 1] // w
            assert (np.abs(dx) + np.abs(dy) <= 1).all(), f"illegal move at t={t}"


@pytest.mark.parametrize("grid,na,nt", [
    (Grid.from_ascii("\n".join(["." * 12] * 12)), 6, 5),
    (Grid.random_obstacles(16, 16, 0.2, seed=9), 5, 6),
])
def test_parallel_solver_invariants_and_completion(grid, na, nt):
    starts, tasks = _scenario(grid, na, nt, seed=2)
    paths_pos, paths_state, makespan = solve_offline(grid, starts, tasks)
    assert 0 < makespan <= 2000, "solver hit the horizon cap"
    _check_paths(grid, paths_pos)
    # starts respected: first recorded step is one move from the start
    delta0 = np.abs(paths_pos[0] - starts)
    assert np.isin(delta0, [0, 1, grid.width]).all()


def test_parallel_vs_oracle_makespan():
    grid = Grid.from_ascii("\n".join(["." * 14] * 14))
    ratios = []
    for seed in range(3):
        starts, tasks = _scenario(grid, 6, 6, seed=seed)
        oracle = OracleSim(grid, starts, tasks)
        mk_oracle = oracle.run()
        oracle.assert_no_collisions()
        assert oracle.task_used.all()
        _, _, mk_par = solve_offline(grid, starts, tasks)
        assert mk_par <= 2000 and mk_oracle <= 2000
        ratios.append(mk_par / mk_oracle)
    # parallel ordering differs from sequential; stay within a modest factor
    assert np.mean(ratios) < 1.5, f"makespan ratios {ratios}"


@pytest.mark.parametrize("grid_fn,na,nt,thresh", [
    # the reference's own comfortable envelope (manager.rs:564-567 scale);
    # threshold from PARITY.md (mean 1.065 over 10 seeds, margin on top)
    (Grid.default, 50, 50, 1.3),
    # congested warehouse aisles
    (lambda: Grid.warehouse(64, 64), 40, 40, 1.3),
])
def test_parity_at_reference_envelope(grid_fn, na, nt, thresh):
    """Oracle-vs-parallel parity at the reference's deployment scale and on
    congested maps (VERDICT r1 item 5); the full 10-seed table is
    PARITY.md (analysis/parity_table.py).  Seeds where the ORACLE deadlocks
    (the reference's shared-delivery flaw, fixed by our push extension)
    count as wins for the parallel solver and skip the ratio."""
    grid = grid_fn()
    ratios = []
    for seed in range(3):
        starts, tasks = _scenario(grid, na, nt, seed=seed)
        oracle = OracleSim(grid, starts, tasks)
        mk_oracle = oracle.run()
        oracle.assert_no_collisions()
        _, _, mk_par = solve_offline(grid, starts, tasks)
        assert 0 < mk_par <= 2000, "parallel solver must always complete"
        if oracle.task_used.all() and mk_oracle <= 2000:
            ratios.append(mk_par / mk_oracle)
    assert ratios, "oracle deadlocked on every seed"
    assert np.mean(ratios) < thresh, f"makespan ratios {ratios}"


def test_push_extension_resolves_shared_delivery_deadlock():
    """Two tasks delivering to the same cell: the first deliverer parks on
    it and the reference (= oracle) deadlocks — its Rule-3 swap exchanges
    identical goals (tswap.rs:197-202).  The parallel solver's documented
    push extension (solver/step.py) must complete."""
    grid = Grid.from_ascii("." * 6)
    starts = np.array([grid.idx((0, 0)), grid.idx((5, 0))], np.int32)
    tasks = np.array([[grid.idx((0, 0)), grid.idx((3, 0))],
                      [grid.idx((5, 0)), grid.idx((3, 0))]], np.int32)
    oracle = OracleSim(grid, starts, tasks)
    mk_oracle = oracle.run()
    assert mk_oracle > 2000 or not oracle.task_used.all(), (
        "expected the reference semantics to deadlock on this instance")
    paths, _, mk = solve_offline(grid, starts, tasks)
    assert 0 < mk < 50, "push extension failed to resolve the deadlock"
    _check_paths(grid, paths)
    # the carrying agent must PHYSICALLY reach the contested delivery cell
    # (Rule 4 must not rotate the push away; the pair mutual-swaps instead)
    assert (paths[:, 0] == grid.idx((3, 0))).any(), (
        "agent 0 never physically reached its delivery cell")


def test_solver_completes_all_tasks():
    grid = Grid.from_ascii("\n".join(["." * 12] * 12))
    starts, tasks = _scenario(grid, 4, 8, seed=5)
    cfg = SolverConfig(height=12, width=12, num_agents=4)
    final = run_mapd(cfg, jnp.asarray(starts), jnp.asarray(tasks),
                     jnp.asarray(grid.free))
    assert bool(final.task_used.all())
    assert (np.asarray(final.phase) == AgentPhase.IDLE).all()
    assert int(final.t) <= cfg.max_timesteps


def test_solver_deterministic():
    grid = Grid.random_obstacles(12, 12, 0.15, seed=3)
    starts, tasks = _scenario(grid, 4, 4, seed=7)
    p1, s1, m1 = solve_offline(grid, starts, tasks)
    p2, s2, m2 = solve_offline(grid, starts, tasks)
    assert m1 == m2
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(s1, s2)


def test_congested_corridor_resolves():
    # two agents in a dead-end corridor must swap via TSWAP rules, not deadlock
    grid = Grid.from_ascii("@@@@@@\n@....@\n@@@@@@")
    starts = np.array([grid.idx((1, 1)), grid.idx((4, 1))], np.int32)
    # tasks send each agent to the other's side
    tasks = np.array([
        [grid.idx((4, 1)), grid.idx((1, 1))],
        [grid.idx((1, 1)), grid.idx((4, 1))],
    ], np.int32)
    paths_pos, _, makespan = solve_offline(grid, starts, tasks)
    assert makespan <= 2000
    _check_paths(grid, paths_pos)


def test_host_prime_matches_fused_prime():
    """mapd.host_prime_fields (the per-chunk burst used at EXTREME-class
    grids) must produce bit-identical fields to the fused
    prime_fields scan."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_distributed_tswap_tpu.core.sampling import start_positions_array
    from p2p_distributed_tswap_tpu.core.tasks import TaskGenerator
    from p2p_distributed_tswap_tpu.solver import mapd

    grid = Grid.random_obstacles(24, 24, 0.1, seed=1)
    n = 10
    cfg = SolverConfig(height=24, width=24, num_agents=n, replan_chunk=4)
    starts = start_positions_array(grid, n, seed=0)
    tasks = TaskGenerator(grid, seed=1).generate_task_arrays(12)
    free = jnp.asarray(grid.free)
    s0, _ = jax.jit(functools.partial(mapd.prepare_state_unprimed, cfg))(
        jnp.asarray(starts, jnp.int32), jnp.asarray(tasks, jnp.int32))
    fused = mapd.prime_fields(cfg, s0, free)
    hosted = mapd.host_prime_fields(cfg, s0, free)
    np.testing.assert_array_equal(np.asarray(fused.dirs),
                                  np.asarray(hosted.dirs))
    assert not np.asarray(hosted.need_replan).any()
