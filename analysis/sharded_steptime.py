"""Virtual-mesh step-time comparison: single device vs 8-way agent-sharded
vs 2x4 (agents x tiles) — VERDICT r2 item 4.

This measures on the virtual CPU mesh, not on chips: the sharded step's
OVERHEAD — collective next-hop
psum, sharded replan bookkeeping, halo-exchanged sweeps — is measured on
the same 8-device virtual CPU mesh the correctness tests use.  The box
has ONE physical core, so the 8 "devices" serialize: the ratio
sharded/single measures TOTAL WORK added by sharding (collectives +
bookkeeping), not parallel wall-clock — on real chips the sharded per-step
time would be roughly (single-device work / n_devices) + the overhead this
table isolates.  The config is sized for a 1-core box.

Usage: python analysis/sharded_steptime.py [--steps K]
Prints one aligned table; paste into SCALING.md.
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from p2p_distributed_tswap_tpu.parallel.virtual_mesh import pin_cpu_backend  # noqa: E402

DEVICES = pin_cpu_backend(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from p2p_distributed_tswap_tpu.core.config import SolverConfig  # noqa: E402
from p2p_distributed_tswap_tpu.core.grid import Grid  # noqa: E402
from p2p_distributed_tswap_tpu.core.sampling import (  # noqa: E402
    start_positions_array)
from p2p_distributed_tswap_tpu.core.tasks import TaskGenerator  # noqa: E402
from p2p_distributed_tswap_tpu.parallel import (  # noqa: E402
    sharded, sharded2d)
from p2p_distributed_tswap_tpu.parallel.mesh import (  # noqa: E402
    TILES_AXIS, agent_mesh, agent_tile_mesh)
from p2p_distributed_tswap_tpu.solver import mapd  # noqa: E402

WARMUP = 8


def _measure(step, s, tasks, free, steps):
    for _ in range(WARMUP):
        s = step(s, tasks, free)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    for _ in range(steps):
        s = step(s, tasks, free)
    jax.block_until_ready(s)
    return 1000.0 * (time.perf_counter() - t0) / steps, s


def build_problem():
    grid = Grid.random_obstacles(128, 128, 0.1, seed=0)
    n = 128
    cfg = SolverConfig(height=128, width=128, num_agents=n, replan_chunk=32)
    starts = start_positions_array(grid, n, seed=0)
    tasks = TaskGenerator(grid, seed=1).generate_task_arrays(n)
    return (grid, cfg, jnp.asarray(starts, jnp.int32),
            jnp.asarray(tasks, jnp.int32), jnp.asarray(grid.free))


def bench_single(cfg, starts, tasks, free, steps):
    step = jax.jit(functools.partial(mapd.mapd_step, cfg))
    s, tasks = jax.jit(functools.partial(mapd.prepare_state, cfg))(
        starts, tasks, free)
    return _measure(step, s, tasks, free, steps)


def _prep_replicated(cfg, starts, tasks):
    s, _ = mapd.prepare_state_unprimed(cfg, starts, tasks)
    return s


def bench_sharded(cfg, starts, tasks, free, steps):
    mesh = agent_mesh(devices=DEVICES)
    specs = sharded.agent_state_specs()
    sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    step = jax.jit(sm(functools.partial(sharded.sharded_mapd_step, cfg),
                      in_specs=(specs, P(), P()), out_specs=specs))
    prime = jax.jit(sm(functools.partial(sharded._sharded_prime, cfg),
                       in_specs=(specs, P()), out_specs=specs))
    s = _prep_replicated(cfg, starts, tasks)
    s = jax.device_put(s, jax.tree.map(
        lambda sp: jax.sharding.NamedSharding(mesh, sp), specs))
    s = prime(s, free)
    return _measure(step, s, tasks, free, steps)


def bench_sharded2d(cfg, starts, tasks, free, steps):
    mesh = agent_tile_mesh(2, 4, devices=DEVICES)
    specs = sharded2d.state_specs_2d()
    sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    step = jax.jit(sm(functools.partial(sharded2d.sharded2d_mapd_step, cfg),
                      in_specs=(specs, P(), P(TILES_AXIS, None)),
                      out_specs=specs))
    prime = jax.jit(sm(functools.partial(sharded2d._prime_2d, cfg),
                       in_specs=(specs, P(TILES_AXIS, None)),
                       out_specs=specs))
    s = _prep_replicated(cfg, starts, tasks)
    s = jax.device_put(s, jax.tree.map(
        lambda sp: jax.sharding.NamedSharding(mesh, sp), specs))
    s = prime(s, free)
    return _measure(step, s, tasks, free, steps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=25)
    args = ap.parse_args()
    grid, cfg, starts, tasks, free = build_problem()
    print(f"# config: {cfg.num_agents} agents, {grid.height}x{grid.width} "
          f"random-obstacle grid, {int(tasks.shape[0])} tasks, "
          f"replan_chunk_small={cfg.replan_chunk_small}, "
          f"{args.steps} measured steps on the 8-device virtual CPU mesh")
    rows = []
    ms1, s1 = bench_single(cfg, starts, tasks, free, args.steps)
    rows.append(("single-device", ms1, 1.0))
    ms8, s8 = bench_sharded(cfg, starts, tasks, free, args.steps)
    rows.append(("sharded 8 (agents)", ms8, ms8 / ms1))
    ms2d, s2d = bench_sharded2d(cfg, starts, tasks, free, args.steps)
    rows.append(("sharded 2x4 (agents x tiles)", ms2d, ms2d / ms1))
    # same trajectory on every variant (bit-identity spot check)
    import numpy as np
    assert np.array_equal(np.asarray(s1.pos), np.asarray(s8.pos)), \
        "sharded-8 diverged from single-device"
    assert np.array_equal(np.asarray(s1.pos), np.asarray(s2d.pos)), \
        "sharded-2x4 diverged from single-device"
    print(f"{'variant':<30} {'ms/step':>9} {'vs single':>10}")
    for name, ms, ratio in rows:
        print(f"{name:<30} {ms:>9.2f} {ratio:>9.2f}x")
    print("# positions bit-identical across all variants after "
          f"{WARMUP + args.steps} steps")


if __name__ == "__main__":
    main()
