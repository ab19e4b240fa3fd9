"""The reference agrees with itself and with the program where the
program is sound (CPU, small sizes)."""

import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference, world


def test_host_and_device_bfs_agree():
    free = world.warehouse(64, 64)
    goals = np.flatnonzero(free.reshape(-1))[[0, 77, 1500, 2900]]
    dev = reference.bfs_device(free, goals, block=3)
    for r, g in enumerate(goals):
        host = reference.bfs(free, int(g))
        host = np.where(host >= reference.INF, reference.INF, host)
        assert np.array_equal(dev[r], host.astype(np.int32))


LORR_SMALL = {"kind": "warehouse", "height": 28, "width": 100, "shelf_h": 2,
              "shelf_w": 20, "aisle": 2, "margin": 1}


@pytest.mark.parametrize("mapspec,n,orders,seed", [
    ({"kind": "empty", "height": 100, "width": 100}, 50, 50, 0),
    ({"kind": "empty", "height": 100, "width": 100}, 50, 50, 2**33 + 1),
    ({"kind": "warehouse", "height": 64, "width": 64}, 400, 400, 3),
    # the LoRR cell's layout, density (26%) and backlog (3 orders a
    # robot), at a fifth of its side; its field rows get compacted
    (LORR_SMALL, 440, 1320, 2**31 + 11)])
def test_sim_matches_program(mapspec, n, orders, seed):
    from p2p_distributed_tswap_tpu.core.config import SolverConfig
    from p2p_distributed_tswap_tpu.solver import mapd

    free = world.build_map(mapspec)
    h, w = free.shape
    starts, orders = world.fleet(free, n, orders, seed)
    cfg = SolverConfig(height=h, width=w, num_agents=n,
                       replan_chunk=min(64, n))
    f = mapd._run_mapd_jit(cfg, jnp.asarray(starts), jnp.asarray(orders),
                           jnp.asarray(free))
    t = int(f.t)
    rt, rp, rs = reference.Sim(free, starts, orders).run()
    assert t == rt
    assert np.array_equal(np.asarray(f.paths_pos[:t]), rp)
    assert np.array_equal(np.asarray(f.paths_state[:t]), rs)


def test_vector_hops_match_scalar_hop():
    """Sim's hops for all agents at once equal :func:`reference.hop`
    agent by agent, with fields from the device BFS."""
    free = world.build_map(LORR_SMALL)
    starts, orders = world.fleet(free, 300, 300, 5)
    sim = reference.Sim(free, starts, orders, fields=lambda g:
                        reference.bfs_device(free, g, block=64))
    for _ in range(3):
        sim.step()
    got = sim._next_hops(sim.slot, sim.pos)
    want = [reference.hop(reference.bfs(free, int(sim.row_goal[sim.slot[i]])),
                          int(sim.pos[i]), *free.shape)
            for i in range(sim.n)]
    assert np.array_equal(got, want)
