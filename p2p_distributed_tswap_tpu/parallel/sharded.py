"""Agent-axis sharded TSWAP solver (shard_map + ICI collectives).

This is the TPU-native replacement for the reference's scale-out story
(SURVEY §2 strategy table): where the reference runs one OS process per agent
and floods every position update over a gossipsub mesh (O(N^2) messages,
DECENTRALIZED_ISSUES.md:21-25), here the **direction fields** — O(N * H * W)
bytes, the only state — are sharded across devices by field row, and each
step exchanges exactly O(N) bytes over ICI:

- ``pos/goal/slot/phase`` (a few int32 per agent) are replicated; every device
  runs the identical deterministic rule phases, so no collective is needed for
  conflict resolution.
- The per-agent next-hop lookup ``dirs[slot[i], pos[i]]`` is the one truly
  distributed access (an agent's field row can live on any device).  Each
  device reads the rows it owns for whichever agents hold them and a single
  ``psum`` assembles the (N,) direction-code vector — the moral equivalent of
  the reference's "broadcast position, receive neighbor positions" tick
  (src/bin/decentralized/agent.rs:730-789) at 1 byte per agent per hop.
- Replanning shards naturally: each device recomputes only field rows it owns
  (fast-sweeping over its own (R, H, W) batch) — the proposed-but-never-built
  geographic partitioning of the reference (DECENTRALIZED_ISSUES.md:62-96),
  realized as data parallelism over fields.

``num_agents`` must be divisible by the mesh size (pad with parked agents at
distinct free cells if needed).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.core.grid import Grid
from p2p_distributed_tswap_tpu.ops.distance import (
    apply_direction,
    direction_fields,
    gather_packed,
    pack_directions,
    packed_cells,
)
from p2p_distributed_tswap_tpu.parallel.mesh import AGENTS_AXIS, agent_mesh
from p2p_distributed_tswap_tpu.solver import mapd as mapd_mod
from p2p_distributed_tswap_tpu.solver.mapd import MapdState, init_state


def _sharded_next_hops(cfg: SolverConfig, dirs_local: jnp.ndarray,
                       slot: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Distributed ``dirs[slot[i], pos[i]]``: one psum of (N,) int32."""
    n = cfg.num_agents
    rows_local = dirs_local.shape[0]
    shard = jax.lax.axis_index(AGENTS_AXIS)
    # inverse of the slot permutation: which agent holds each field row
    inv = jnp.zeros(n, jnp.int32).at[slot].set(jnp.arange(n, dtype=jnp.int32))
    rows = jnp.arange(rows_local, dtype=jnp.int32)
    holders = inv[shard * rows_local + rows]          # (L,) agent per local row
    vals = gather_packed(dirs_local, rows, pos[holders])  # (L,) uint8 codes
    contrib = jnp.zeros(n, jnp.int32).at[holders].set(vals.astype(jnp.int32))
    codes = jax.lax.psum(contrib, AGENTS_AXIS).astype(jnp.uint8)
    return apply_direction(pos, codes, cfg.width)


def _sharded_prime(cfg: SolverConfig, s: MapdState, free: jnp.ndarray
                   ) -> MapdState:
    """The t=0 field burst, sharded: every device computes ALL field rows it
    owns in WIDE ``replan_chunk`` batches (one fixed-trip lax.scan) — the
    distributed twin of mapd.prime_fields.  Hoisting the burst out of the
    per-step loop is what lets the steady-state replan below run the NARROW
    chunk: with the wide chunk in the loop, every step at scale pays a
    ~wide-sweep's worth of wasted width for a handful of dirty rows
    (VERDICT r2 weak item 3; measured 152 vs 328 ms/step single-device)."""
    n = cfg.num_agents
    dirs_local = s.dirs
    rows_local = dirs_local.shape[0]
    shard = jax.lax.axis_index(AGENTS_AXIS)
    # which agent holds each of my field rows (inverse slot permutation)
    inv = jnp.zeros(n, jnp.int32).at[s.slot].set(
        jnp.arange(n, dtype=jnp.int32))
    r = min(cfg.replan_chunk, rows_local)
    nchunks = -(-rows_local // r)
    lane = jnp.arange(r, dtype=jnp.int32)

    def chunk(dirs_local, ci):
        row_local = jnp.clip(ci * r + lane, 0, rows_local - 1)
        holder = inv[shard * rows_local + row_local]
        fields = direction_fields(free, s.goal[holder],
                                  max_rounds=cfg.max_sweep_rounds)
        dirs_local = dirs_local.at[row_local].set(
            pack_directions(fields.reshape(r, cfg.num_cells)))
        return dirs_local, None

    dirs_local, _ = jax.lax.scan(chunk, dirs_local,
                                 jnp.arange(nchunks, dtype=jnp.int32))
    return s.replace(dirs=dirs_local,
                     need_replan=jnp.zeros_like(s.need_replan))


def _sharded_replan(cfg: SolverConfig, s: MapdState, free: jnp.ndarray
                    ) -> MapdState:
    """Each device recomputes the stale field rows it owns; drains fully.
    Narrow steady-state chunk — the t=0 burst goes through _sharded_prime."""
    n = cfg.num_agents
    dirs_local = s.dirs
    rows_local = dirs_local.shape[0]
    shard = jax.lax.axis_index(AGENTS_AXIS)
    idx = jnp.arange(n, dtype=jnp.int32)
    r = min(cfg.replan_chunk_small, n)
    own = s.need_replan & (s.slot // rows_local == shard)

    def cond(carry):
        _, own = carry
        return jnp.any(own)

    def body(carry):
        dirs_local, own = carry
        priority = jnp.where(own, idx, n)
        sel = -jax.lax.top_k(-priority, r)[0]
        valid = sel < n
        selc = jnp.clip(sel, 0, n - 1)
        fields = direction_fields(free, s.goal[selc],
                                  max_rounds=cfg.max_sweep_rounds)
        fields = pack_directions(fields.reshape(r, cfg.num_cells))
        # local row index; invalid lanes go to a scratch row (no OOB scatter)
        local_row = jnp.where(valid, s.slot[selc] - shard * rows_local,
                              rows_local)
        padded = jnp.concatenate(
            [dirs_local,
             jnp.zeros((1, packed_cells(cfg.num_cells)), dirs_local.dtype)])
        dirs_local = padded.at[local_row].set(fields)[:rows_local]
        cleared = jnp.zeros(n, bool).at[selc].max(valid)
        return dirs_local, own & ~cleared

    dirs_local, _ = jax.lax.while_loop(cond, body, (dirs_local, own))
    # every stale row is owned by exactly one device, so the union drains all
    return s.replace(dirs=dirs_local,
                     need_replan=jnp.zeros_like(s.need_replan))


def _nh_factory(cfg: SolverConfig, dirs_local: jnp.ndarray):
    return functools.partial(_sharded_next_hops, cfg, dirs_local)


def sharded_mapd_step(cfg: SolverConfig, s: MapdState, tasks: jnp.ndarray,
                      free: jnp.ndarray) -> MapdState:
    """One MAPD timestep inside shard_map: the single-device MAPD sequencing
    (mapd.mapd_step) with the distributed replan and next-hop lookup swapped
    in — replicated control flow, sharded fields."""
    return mapd_mod.mapd_step(cfg, s, tasks, free,
                              replan_fn=_sharded_replan,
                              nh_factory=_nh_factory)


def agent_state_specs() -> MapdState:
    """shard_map partition specs for MapdState on the 1-D agent mesh: only
    the direction-field rows shard (the dominant buffer); every (N,) vector
    and the stale-view fields are replicated (they feed replicated rule
    phases).  Single source of truth for every 1-D-mesh entry point
    (__graft_entry__, analysis/sharded_steptime.py)."""
    return MapdState(
        pos=P(), goal=P(), slot=P(), dirs=P(AGENTS_AXIS, None), phase=P(),
        agent_task=P(), task_used=P(), need_replan=P(), t=P(),
        paths_pos=P(), paths_state=P(),
        vpos=P(), vgoal=P(), vstamp=P(), pend_from=P(), pend_push=P())


def make_sharded_runner(cfg: SolverConfig, mesh: Mesh | None = None,
                        num_tasks: int | None = None):
    """Build a jitted sharded end-to-end MAPD solve over ``mesh``.

    Returns ``run(starts (N,), tasks (T,2), free (H,W)) -> MapdState``.
    """
    if mesh is None:
        mesh = agent_mesh()
    n_dev = mesh.devices.size
    assert cfg.num_agents % n_dev == 0, (
        f"num_agents={cfg.num_agents} must divide over {n_dev} devices")

    state_specs = agent_state_specs()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(state_specs, P(), P()), out_specs=state_specs,
        check_vma=False)
    def run_shard(s, tasks, free):
        s = _sharded_prime(cfg, s, free)  # wide t=0 burst, off the hot loop

        def cond(s):
            return ~mapd_mod._finished(cfg, s)

        def body(s):
            return sharded_mapd_step(cfg, s, tasks, free)

        return jax.lax.while_loop(cond, body, s)

    @jax.jit
    def run(starts, tasks, free):
        if tasks.shape[0] == 0:
            # same trace-safety device as mapd.run_mapd: one pre-used dummy
            tasks = jnp.zeros((1, 2), jnp.int32)
            s = init_state(cfg, starts, 1)
            s = s.replace(task_used=jnp.ones(1, bool))
        else:
            s = init_state(cfg, starts, tasks.shape[0])
        # pre-loop transitions + first assignment, matching
        # mapd.prepare_state's ordering (an agent starting ON its assigned
        # pickup flips to delivery in the first step's transitions) so
        # sharded runs stay bit-identical to the single-device solver;
        # both are replicated ops, no collectives needed
        s = mapd_mod._transitions(cfg, s, tasks)
        s = mapd_mod._assign(cfg, s, tasks)
        return run_shard(s, tasks, free)

    return run


def solve_offline_sharded(grid: Grid, starts_idx: np.ndarray,
                          tasks: np.ndarray, cfg: SolverConfig | None = None,
                          mesh: Mesh | None = None
                          ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Sharded counterpart of mapd.solve_offline (same contract)."""
    if cfg is None:
        cfg = SolverConfig(height=grid.height, width=grid.width,
                           num_agents=len(starts_idx))
    mapd_mod.validate_starts(grid, starts_idx)
    mapd_mod.validate_tasks(grid, tasks)
    run = make_sharded_runner(cfg, mesh)
    final = run(jnp.asarray(starts_idx, jnp.int32),
                jnp.asarray(tasks, jnp.int32), jnp.asarray(grid.free))
    makespan = int(final.t)
    if not cfg.record_paths:
        n = len(starts_idx)
        return (np.zeros((0, n), np.int32), np.zeros((0, n), np.int8),
                makespan)
    return (np.asarray(final.paths_pos[:makespan]),
            np.asarray(final.paths_state[:makespan]), makespan)
