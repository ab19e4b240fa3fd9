"""The program's decentralized stale step equals the benchmark's plain
reference of it (``benchmark/harness/reference_stale.py`` ``StaleSim``),
step for step: every agent's position, goal, phase and field slot after
every step, on the CPU at small sizes.  The reference is loaded from the
benchmark's directory, as the benchmark's own tests load it."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.solver import mapd

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))    # last: its ``tests`` and ``run`` shadow nothing

from harness import reference_stale, world  # noqa: E402

# the LoRR floor's layout at a fifth of its side: 440 robots at ~26% of
# the free cells, a backlog of three orders a robot
SHELVES = {"kind": "warehouse", "height": 28, "width": 100, "shelf_h": 2,
           "shelf_w": 20, "aisle": 2, "margin": 1}
SQUARE = {"kind": "empty", "height": 20, "width": 20}


def _corridor() -> np.ndarray:
    """A 1-wide corridor of 30 cells with a one-cell bay every three
    cells, alternately above and below: robots meet face to face."""
    free = np.zeros((3, 30), dtype=bool)
    free[1] = True
    free[0, ::6] = True
    free[2, 3::6] = True
    return free


# (map, robots, orders, instance seed, solver knobs, steps)
CASES = {
    "shelves-r2-d1": (SHELVES, 440, 1320, 2**31 + 11,
                      dict(view_refresh_steps=2, swap_commit_delay=1), 40),
    "shelves-r3-d0": (SHELVES, 440, 1320, 7,
                      dict(view_refresh_steps=3, swap_commit_delay=0), 40),
    "shelves-r1-d1": (SHELVES, 440, 1320, 3,
                      dict(view_refresh_steps=1, swap_commit_delay=1), 40),
    "square-r2-d0": (SQUARE, 100, 300, 5,
                     dict(view_refresh_steps=2, swap_commit_delay=0), 60),
    "square-r3-d1": (SQUARE, 100, 300, 2**40 + 3,
                     dict(view_refresh_steps=3, swap_commit_delay=1), 60),
    # a radius of 2 and rings of at most 4: rings the initiator sees only
    # in part, and rings of the longest length the walk finds
    "square-near-rings": (SQUARE, 120, 360, 9,
                          dict(view_refresh_steps=3, swap_commit_delay=1,
                               visibility_radius=2, cycle_cap=3), 60),
    # face-offs (rings of 2, the longest at ``cycle_cap`` 1) that only
    # one of the two robots sees whole at a radius of 1
    "square-ring-cap": (SQUARE, 120, 360, 4,
                        dict(view_refresh_steps=3, swap_commit_delay=1,
                             visibility_radius=1, cycle_cap=1), 40),
    "corridor-r2-d1": (None, 8, 24, 1,
                       dict(view_refresh_steps=2, swap_commit_delay=1), 80),
    "corridor-r3-d0": (None, 8, 24, 4,
                       dict(view_refresh_steps=3, swap_commit_delay=0), 80),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stale_step_equals_reference(case):
    spec, n, orders, seed, knobs, steps = CASES[case]
    free = _corridor() if spec is None else world.build_map(spec)
    h, w = free.shape
    starts, tasks = world.fleet(free, n, orders, seed)
    solver = dict({"visibility_radius": 15, "max_timesteps": 400}, **knobs)
    cfg = SolverConfig(height=h, width=w, num_agents=n,
                       replan_chunk=min(16, n), record_paths=False,
                       **solver)
    assert cfg.stale_mode
    sim = reference_stale.StaleSim(free, starts, tasks,
                                   **reference_stale.sim_options(solver))
    s, dev_tasks = jax.jit(mapd.prepare_state, static_argnums=0)(
        cfg, jnp.asarray(starts), jnp.asarray(tasks), jnp.asarray(free))
    step = jax.jit(mapd.mapd_step, static_argnums=0)
    free_d = jnp.asarray(free)
    for t in range(steps + 1):
        if t:
            s = step(cfg, s, dev_tasks, free_d)
            sim.step()
        for name, want in (("pos", sim.pos), ("goal", sim.goal),
                           ("phase", sim.phase), ("slot", sim.slot)):
            got = np.asarray(getattr(s, name), dtype=np.int64)
            bad = np.flatnonzero(got != want)
            assert not len(bad), (f"step {t}: {name} differs for agents "
                                  f"{bad[:8]}: {got[bad[:8]]} against "
                                  f"{want[bad[:8]]}")
    # the case exercised what it compares: robots moved and goals were
    # exchanged
    assert sum(sim.exchanges) > 0
    assert not np.array_equal(sim.pos, starts)


@pytest.mark.parametrize("solver,match", [
    ({"visibility_radius": 15, "view_refresh_steps": 2,
      "view_ttl_steps": 20}, "view_ttl_steps"),
    ({"visibility_radius": 15}, "not the stale"),
    ({"view_refresh_steps": 2}, "not the stale"),
    ({"visibility_radius": 15, "swap_commit_delay": 1, "max_ages": 3},
     "does not model")])
def test_reference_refuses_what_it_does_not_model(solver, match):
    with pytest.raises(ValueError, match=match):
        reference_stale.sim_options(solver)
