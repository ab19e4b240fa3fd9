"""2-D (agents x tiles) sharded MAPD solver — the EXTREME deployment shape.

Composes the framework's two shardings (SCALING.md):

- **agents axis** (parallel/sharded.py): direction-field ROWS shard across
  one mesh dimension — each device block owns N / A field rows.
- **tiles axis** (ops/tiled_distance.py): each field row's CELLS shard
  across the other dimension as horizontal grid bands — each device holds
  (N/A rows) x (H/T band), so per-device field residency shrinks by the
  full mesh size A*T, and the sweep's transient workspace by T.

Control state (pos/goal/slot/phase, a few int32 per agent) stays replicated;
every device runs the identical deterministic rule phases.  The two
distributed pieces per step:

- **next-hop lookup** ``dirs[slot[i], pos[i] nibble]``: the device holding
  both agent i's field row (agents axis) and the band containing ``pos[i]``
  (tiles axis) contributes the code; a single psum over BOTH axes assembles
  the replicated (N,) vector — still O(N) bytes over ICI per step.
- **replanning**: all devices of an agent block select the same stale rows
  (replicated inputs, deterministic top-k); each computes its own BAND of
  the new fields with the halo-exchanged tiled sweep
  (ops/tiled_distance.tiled_direction_fields over the tiles axis) and
  writes its (rows x band) block.

Results are bit-identical to the single-device solver
(tests/test_sharded2d.py) — sharding is purely a capacity/bandwidth lever.

Constraints: ``num_agents % A == 0``, ``H % T == 0``, and the per-band cell
count ``(H/T) * W`` must be a multiple of 8 (whole packed uint32 words per
band; true whenever W is a multiple of 8).
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
    pack_directions,
)
from p2p_distributed_tswap_tpu.ops.tiled_distance import (
    tiled_direction_fields,
)
from p2p_distributed_tswap_tpu.parallel.mesh import (
    AGENTS_AXIS,
    TILES_AXIS,
    agent_tile_mesh,
)
from p2p_distributed_tswap_tpu.solver import mapd as mapd_mod
from p2p_distributed_tswap_tpu.solver.mapd import MapdState, init_state


def _next_hops_2d(cfg: SolverConfig, dirs_local: jnp.ndarray,
                  slot: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Distributed ``dirs[slot[i], pos[i]]`` on the 2-D mesh: one psum over
    (agents, tiles) of an (N,) int32 contribution vector."""
    n = cfg.num_agents
    rows_local, words_local = dirs_local.shape
    a_shard = jax.lax.axis_index(AGENTS_AXIS)
    t_shard = jax.lax.axis_index(TILES_AXIS)
    # which agent holds each of my field rows (inverse slot permutation)
    inv = jnp.zeros(n, jnp.int32).at[slot].set(jnp.arange(n, dtype=jnp.int32))
    rows = jnp.arange(rows_local, dtype=jnp.int32)
    holders = inv[a_shard * rows_local + rows]        # (L,) agent per row
    p = pos[holders]
    word_global = p >> 3
    in_band = ((word_global >= t_shard * words_local)
               & (word_global < (t_shard + 1) * words_local))
    word = dirs_local[rows, jnp.clip(word_global - t_shard * words_local,
                                     0, words_local - 1)]
    code = (word >> ((p & 7) * 4).astype(jnp.uint32)) & 0xF
    contrib = jnp.zeros(n, jnp.int32).at[holders].set(
        jnp.where(in_band, code.astype(jnp.int32), 0))
    codes = jax.lax.psum(contrib, (AGENTS_AXIS, TILES_AXIS)).astype(jnp.uint8)
    return apply_direction(pos, codes, cfg.width)


def _prime_2d(cfg: SolverConfig, s: MapdState, free_local: jnp.ndarray
              ) -> MapdState:
    """The t=0 field burst on the 2-D mesh: every agent block computes ALL
    its rows in WIDE ``replan_chunk`` batches, each tiles-axis device
    sweeping its band (see parallel/sharded.py::_sharded_prime for why the
    burst is hoisted off the steady-state path).  The trip count
    ceil(rows_local / r) is identical on every device — rows_local is
    uniform — so the tiled sweep's collective schedule lines up with no
    pmax needed."""
    dirs_local = s.dirs
    rows_local, words_local = dirs_local.shape
    a_shard = jax.lax.axis_index(AGENTS_AXIS)
    # the inverse slot permutation as an argsort: the scatter that
    # _next_hops_2d uses, fed init_state's iota slots here, aborts the
    # v5e compiler (scatter_emitter.cc: operand_indices.size() == 1)
    inv = jnp.argsort(s.slot).astype(jnp.int32)
    r = min(cfg.replan_chunk, rows_local)
    nchunks = -(-rows_local // r)
    lane = jnp.arange(r, dtype=jnp.int32)

    def chunk(dirs_local, ci):
        row_local = jnp.clip(ci * r + lane, 0, rows_local - 1)
        holder = inv[a_shard * rows_local + row_local]
        fields = tiled_direction_fields(
            free_local, s.goal[holder], cfg.width, axis_name=TILES_AXIS,
            max_rounds=cfg.max_sweep_rounds,
            fixpoint_axes=(AGENTS_AXIS, TILES_AXIS))
        dirs_local = dirs_local.at[row_local].set(
            pack_directions(fields.reshape(r, -1)))
        return dirs_local, None

    dirs_local, _ = jax.lax.scan(chunk, dirs_local,
                                 jnp.arange(nchunks, dtype=jnp.int32))
    return s.replace(dirs=dirs_local,
                     need_replan=jnp.zeros_like(s.need_replan))


def _replan_2d(cfg: SolverConfig, s: MapdState, free_local: jnp.ndarray
               ) -> MapdState:
    """Drain stale field rows owned by this agent block; each tiles-axis
    device computes its band via the halo-exchanged tiled sweep.  Narrow
    steady-state chunk — the t=0 burst goes through _prime_2d."""
    n = cfg.num_agents
    dirs_local = s.dirs
    rows_local, words_local = dirs_local.shape
    a_shard = jax.lax.axis_index(AGENTS_AXIS)
    idx = jnp.arange(n, dtype=jnp.int32)
    r = min(cfg.replan_chunk_small, n)
    own = s.need_replan & (s.slot // rows_local == a_shard)

    # The loop body runs tiles-axis collectives (halo exchange + fixpoint
    # psum inside the tiled sweep), so every device MUST execute the same
    # number of rounds — a data-dependent `while any(own)` would give agent
    # blocks with fewer stale rows a shorter collective schedule and
    # deadlock the others.  pmax the per-block round count first; blocks
    # that finish early run no-op rounds (all-invalid lanes write only the
    # scratch row).
    rounds = (jnp.sum(own) + r - 1) // r
    rounds = jax.lax.pmax(rounds, AGENTS_AXIS)

    def body(_, carry):
        dirs_local, own = carry
        priority = jnp.where(own, idx, n)
        sel = -jax.lax.top_k(-priority, r)[0]
        valid = sel < n
        selc = jnp.clip(sel, 0, n - 1)
        fields = tiled_direction_fields(
            free_local, s.goal[selc], cfg.width, axis_name=TILES_AXIS,
            max_rounds=cfg.max_sweep_rounds,
            # uniform sweep schedule across the whole mesh (see _replan_2d's
            # rounds pmax): agent blocks sweep different goal batches, and
            # collectives must line up across them too
            fixpoint_axes=(AGENTS_AXIS, TILES_AXIS))
        fields = pack_directions(fields.reshape(r, -1))  # (r, words_local)
        local_row = jnp.where(valid, s.slot[selc] - a_shard * rows_local,
                              rows_local)
        padded = jnp.concatenate(
            [dirs_local, jnp.zeros((1, words_local), dirs_local.dtype)])
        dirs_local = padded.at[local_row].set(fields)[:rows_local]
        cleared = jnp.zeros(n, bool).at[selc].max(valid)
        return dirs_local, own & ~cleared

    dirs_local, _ = jax.lax.fori_loop(0, rounds, body, (dirs_local, own))
    return s.replace(dirs=dirs_local,
                     need_replan=jnp.zeros_like(s.need_replan))


def _nh_factory_2d(cfg: SolverConfig, dirs_local: jnp.ndarray):
    return functools.partial(_next_hops_2d, cfg, dirs_local)


def sharded2d_mapd_step(cfg: SolverConfig, s: MapdState, tasks: jnp.ndarray,
                        free_local: jnp.ndarray) -> MapdState:
    """One MAPD timestep inside the 2-D shard_map: single-device sequencing
    with the 2-D replan and next-hop lookup swapped in."""
    return mapd_mod.mapd_step(cfg, s, tasks, free_local,
                              replan_fn=_replan_2d,
                              nh_factory=_nh_factory_2d)


def state_specs_2d() -> MapdState:
    return MapdState(
        pos=P(), goal=P(), slot=P(),
        dirs=P(AGENTS_AXIS, TILES_AXIS), phase=P(),
        agent_task=P(), task_used=P(), need_replan=P(), t=P(),
        paths_pos=P(), paths_state=P(),
        vpos=P(), vgoal=P(), vstamp=P(), pend_from=P(), pend_push=P())


def make_sharded2d_runner(cfg: SolverConfig, mesh: Mesh):
    """Jitted end-to-end MAPD solve over a 2-D (agents x tiles) mesh.

    Returns ``run(starts (N,), tasks (T,2), free (H,W)) -> MapdState``.
    """
    n_agent_shards = mesh.shape[AGENTS_AXIS]
    n_tiles = mesh.shape[TILES_AXIS]
    assert cfg.num_agents % n_agent_shards == 0, (
        f"num_agents={cfg.num_agents} must divide over {n_agent_shards} "
        "agent shards")
    assert cfg.height % n_tiles == 0, (
        f"height={cfg.height} must divide over {n_tiles} tiles")
    band_cells = (cfg.height // n_tiles) * cfg.width
    assert band_cells % 8 == 0, (
        f"band cell count {band_cells} must be a multiple of 8 "
        "(whole packed words per band)")

    specs = state_specs_2d()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(specs, P(), P(TILES_AXIS, None)), out_specs=specs,
        check_vma=False)
    def run_shard(s, tasks, free_local):
        s = _prime_2d(cfg, s, free_local)  # wide t=0 burst, off the hot loop

        def cond(s):
            return ~mapd_mod._finished(cfg, s)

        def body(s):
            return sharded2d_mapd_step(cfg, s, tasks, free_local)

        return jax.lax.while_loop(cond, body, s)

    @jax.jit
    def run(starts, tasks, free):
        if tasks.shape[0] == 0:
            tasks = jnp.zeros((1, 2), jnp.int32)
            s = init_state(cfg, starts, 1)
            s = s.replace(task_used=jnp.ones(1, bool))
        else:
            s = init_state(cfg, starts, tasks.shape[0])
        # match mapd.prepare_state's pre-loop transitions + assignment so
        # sharded runs stay bit-identical to the single-device solver
        # (see parallel/sharded.py for the ordering rationale)
        s = mapd_mod._transitions(cfg, s, tasks)
        s = mapd_mod._assign(cfg, s, tasks)
        return run_shard(s, tasks, free)

    return run


def solve_offline_sharded2d(grid: Grid, starts_idx: np.ndarray,
                            tasks: np.ndarray,
                            cfg: SolverConfig | None = None,
                            mesh: Mesh | None = None,
                            n_agent_shards: int = 2, n_tiles: int = 4
                            ) -> Tuple[np.ndarray, np.ndarray, int]:
    """2-D sharded counterpart of mapd.solve_offline (same contract)."""
    if cfg is None:
        cfg = SolverConfig(height=grid.height, width=grid.width,
                           num_agents=len(starts_idx))
    if mesh is None:
        mesh = agent_tile_mesh(n_agent_shards, n_tiles)
    mapd_mod.validate_starts(grid, starts_idx)
    mapd_mod.validate_tasks(grid, tasks)
    run = make_sharded2d_runner(cfg, mesh)
    final = run(jnp.asarray(starts_idx, jnp.int32),
                jnp.asarray(tasks, jnp.int32), jnp.asarray(grid.free))
    makespan = int(final.t)
    if not cfg.record_paths:
        n = len(starts_idx)
        return (np.zeros((0, n), np.int32), np.zeros((0, n), np.int8),
                makespan)
    return (np.asarray(final.paths_pos[:makespan]),
            np.asarray(final.paths_state[:makespan]), makespan)
