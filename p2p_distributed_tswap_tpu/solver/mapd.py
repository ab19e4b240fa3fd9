"""Offline MAPD loop — the TPU equivalent of the reference's ``tswap_mapd``
(src/algorithm/tswap.rs:39-172): greedy nearest-pickup task assignment, the
Idle -> ToPickup -> ToDelivery machine, TSWAP stepping, per-step path
recording, and the all-done-or-horizon termination rule — as one jitted
``lax.while_loop`` over device state.

The one genuinely new mechanism versus the reference is **replanning**: goal
changes from the task lifecycle (assignment, pickup -> delivery) need fresh
direction fields.  Goal *swaps* never do (slot permutation), so the per-step
replan set is small; it is processed in static-size chunks of
``cfg.replan_chunk`` fields per round (fast-sweeping over a (R, H, W) batch),
looping until the set drains.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from p2p_distributed_tswap_tpu.core.agent import AgentPhase, AgentState
from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.core.grid import Grid
from p2p_distributed_tswap_tpu.obs import trace
from p2p_distributed_tswap_tpu.ops.distance import (
    PACKED_STAY,
    direction_fields,
    pack_directions,
    packed_cells,
)
from p2p_distributed_tswap_tpu.solver import step as step_mod
from p2p_distributed_tswap_tpu.solver.step import (
    step_parallel,
    step_with_next_hops,
)

_FAR = jnp.int32(1 << 20)  # > any grid manhattan distance

# Agents per bid pass of the assignment: a round compacts the idle agents'
# ids and scans the backlog for _BID_LANES of them at a time, so its cost
# follows the number of idle agents, not the fleet.  A fleet of at most
# _BID_LANES agents bids all its rows at once (no compaction).
_BID_LANES = 128

# The step's phases as ``jax.named_scope`` names: each op's HLO ``op_name``
# carries the scopes it was traced under (``jit(mapd_step)/replan/while/
# body/sweep/...``), so a device trace can sum op time per phase.  ``sweep``
# and ``pack`` nest inside ``replan``, ``bid`` (one bid pass's own ops,
# once per pass) inside ``assign``; ``rules`` and ``move`` are opened in
# solver/step.py; ``record`` is the step's bookkeeping (the path record, the
# timestep and, in a solve, the done test); ``prime`` is the t=0 burst.  The
# stale step nests three more in ``rules``: ``commit`` (the pending goal
# exchanges landing), ``view`` (the broadcast, the view occupancy and the
# blocker lookup on it) and ``walk`` (the two Rule-4 ring scans).
PHASES = ("assign", "replan", "sweep", "pack", "bid", "rules", "move",
          "record", "prime", "commit", "view", "walk")


@struct.dataclass
class MapdState:
    pos: jnp.ndarray          # (N,) int32 flat cell
    goal: jnp.ndarray         # (N,) int32 flat cell
    slot: jnp.ndarray         # (N,) int32 agent -> field row
    dirs: jnp.ndarray         # (N, ceil(HW/8)) uint32 packed direction fields
    phase: jnp.ndarray        # (N,) int8 AgentPhase
    agent_task: jnp.ndarray   # (N,) int32 task index or -1
    task_used: jnp.ndarray    # (T,) bool
    need_replan: jnp.ndarray  # (N,) bool: agent's goal changed, field stale
    t: jnp.ndarray            # () int32 timestep counter
    paths_pos: jnp.ndarray    # (Tmax+1, N) int32 recorded positions
    paths_state: jnp.ndarray  # (Tmax+1, N) int8 recorded AgentState
    # --- stale/async decentralized view (cfg.stale_mode; inert otherwise,
    # see solver/step.py step_stale) ---
    vpos: jnp.ndarray         # (N,) int32 last-broadcast position
    vgoal: jnp.ndarray        # (N,) int32 last-broadcast goal
    vstamp: jnp.ndarray       # (N,) int32 step of last broadcast
    pend_from: jnp.ndarray    # (N,) int32 pending goal-source permutation
    pend_push: jnp.ndarray    # (N,) int32 pending pushed-goal cell or -1


def init_state(cfg: SolverConfig, starts: jnp.ndarray,
               num_tasks: int) -> MapdState:
    n, hw, tmax = cfg.num_agents, cfg.num_cells, cfg.max_timesteps
    # path buffers shrink to one dummy row when recording is off
    tdim = tmax + 1 if cfg.record_paths else 1
    return MapdState(
        pos=jnp.asarray(starts, jnp.int32),
        goal=jnp.asarray(starts, jnp.int32),
        slot=jnp.arange(n, dtype=jnp.int32),
        dirs=jnp.full((n, packed_cells(hw)), PACKED_STAY, jnp.uint32),
        phase=jnp.full(n, AgentPhase.IDLE, jnp.int8),
        agent_task=jnp.full(n, -1, jnp.int32),
        task_used=jnp.zeros(num_tasks, bool),
        # All rows start stale: an uncomputed all-STAY row is only valid while
        # its agent sits on its start cell, but Rule-3 swaps can hand the row
        # to an agent elsewhere — so every field is computed on the first step.
        need_replan=jnp.ones(n, bool),
        t=jnp.int32(0),
        paths_pos=jnp.zeros((tdim, n), jnp.int32),
        paths_state=jnp.zeros((tdim, n), jnp.int8),
        # everyone "broadcast" at t=0 from their start cell (the reference's
        # occupied/initial-position protocol seeds every cache)
        vpos=jnp.asarray(starts, jnp.int32),
        vgoal=jnp.asarray(starts, jnp.int32),
        vstamp=jnp.zeros(n, jnp.int32),
        pend_from=jnp.arange(n, dtype=jnp.int32),
        pend_push=jnp.full(n, -1, jnp.int32),
    )


def _transitions(cfg: SolverConfig, s: MapdState, tasks: jnp.ndarray) -> MapdState:
    """Arrival transitions (ref tswap.rs:106-121), vectorized: transitions of
    distinct agents are independent, so order does not matter."""
    arrived = s.pos == s.goal
    tp = arrived & (s.phase == AgentPhase.TO_PICKUP)
    td = arrived & (s.phase == AgentPhase.TO_DELIVERY)
    task = jnp.clip(s.agent_task, 0)
    goal = jnp.where(tp, tasks[task, 1], s.goal)
    phase = jnp.where(tp, AgentPhase.TO_DELIVERY,
                      jnp.where(td, AgentPhase.IDLE, s.phase)).astype(jnp.int8)
    agent_task = jnp.where(td, -1, s.agent_task)
    return s.replace(goal=goal, phase=phase, agent_task=agent_task,
                     need_replan=s.need_replan | tp)


def _backlog(cfg: SolverConfig, task_used: jnp.ndarray, tasks: jnp.ndarray):
    """The pickups' (x, y) and the used flags, padded with used tasks to
    whole chunks of ``c = min(assign_chunk, T)``; and ``c`` and the chunk
    count."""
    w, t = cfg.width, tasks.shape[0]
    c = min(cfg.assign_chunk, t)
    nchunks = -(-t // c)
    pad = nchunks * c - t
    px = jnp.pad(tasks[:, 0] % w, (0, pad))
    py = jnp.pad(tasks[:, 0] // w, (0, pad))
    used = jnp.pad(task_used, (0, pad), constant_values=True)
    return px, py, used, c, nchunks


def _nearest_unused(cfg: SolverConfig, pos: jnp.ndarray,
                    task_used: jnp.ndarray, tasks: jnp.ndarray):
    """Per-agent (distance, index) of the nearest unused task pickup,
    Manhattan metric, lowest task index on ties (the reference's
    ``min_by_key`` keeps the first minimum).  Chunked over the task axis so
    transient memory is (N, assign_chunk) int32, never the full (N, T)
    matrix (400 MB at the FLAGSHIP rung, 40 GB at EXTREME)."""
    n, w = cfg.num_agents, cfg.width
    px, py, used, c, nchunks = _backlog(cfg, task_used, tasks)
    ax, ay = pos % w, pos // w

    def chunk(carry, ci):
        best_d, best_k = carry
        o = ci * c
        cpx = jax.lax.dynamic_slice_in_dim(px, o, c)
        cpy = jax.lax.dynamic_slice_in_dim(py, o, c)
        cused = jax.lax.dynamic_slice_in_dim(used, o, c)
        d = (jnp.abs(cpx[None, :] - ax[:, None])
             + jnp.abs(cpy[None, :] - ay[:, None]))
        d = jnp.where(cused[None, :], _FAR, d)
        k = jnp.argmin(d, axis=1).astype(jnp.int32)  # first min in chunk
        dk = jnp.take_along_axis(d, k[:, None], axis=1)[:, 0]
        better = dk < best_d  # strict: ties keep the earlier chunk's index
        return (jnp.where(better, dk, best_d),
                jnp.where(better, o + k, best_k)), None

    init = (jnp.full(n, _FAR, jnp.int32), jnp.zeros(n, jnp.int32))
    (bd, bk), _ = jax.lax.scan(chunk, init,
                               jnp.arange(nchunks, dtype=jnp.int32))
    return bd, bk


def _row_min(d: jnp.ndarray):
    """(minimum, first index of the minimum) of each row of ``d``, out of
    one reduction over (value, index) pairs."""
    k = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)

    def first_min(a, b):
        (da, ka), (db, kb) = a, b
        take_b = (db < da) | ((db == da) & (kb < ka))
        return jnp.where(take_b, db, da), jnp.where(take_b, kb, ka)

    return jax.lax.reduce((d, k), (_FAR, jnp.int32(np.iinfo(np.int32).max)),
                          first_min, (1,))


def _nearest_idle(cfg: SolverConfig, pos: jnp.ndarray, idle: jnp.ndarray,
                  task_used: jnp.ndarray, tasks: jnp.ndarray):
    """:func:`_nearest_unused` for the idle agents alone, ``(_FAR, 0)`` for
    the others.  The idle ids, in ascending order, are bid in
    ceil(#idle / _BID_LANES) passes of _BID_LANES rows, each scanning the
    backlog in chunks of ``assign_chunk`` tasks; fill lanes write to a
    scratch slot.  The chunk is not widened to the (N, assign_chunk)
    budget: a pass over the whole backlog in one (128, T) fusion compiles
    to more device code than the full-width bid it replaces."""
    n, w, lanes = cfg.num_agents, cfg.width, _BID_LANES
    px, py, used, c, nchunks = _backlog(cfg, task_used, tasks)
    count = jnp.cumsum(idle, dtype=jnp.int32)   # idle agents up to each id
    passes = (count[-1] + lanes - 1) // lanes
    rank = jnp.arange(1, lanes + 1, dtype=jnp.int32)

    def bid(carry):
        p, bd, bk = carry
        # the r-th idle agent is the first whose count reaches r; ranks past
        # the last idle agent give n.  A binary search (a loop, so outside
        # ``bid``): nonzero's scatter and a sort compile to more device code
        lane = jnp.searchsorted(count, p * lanes + rank,
                                method="scan").astype(jnp.int32)
        with jax.named_scope("bid"):
            a = pos[jnp.minimum(lane, n - 1)]
        ax, ay = a % w, a // w

        def chunk(best, o):
            best_d, best_k = best
            cpx = jax.lax.dynamic_slice_in_dim(px, o, c)
            cpy = jax.lax.dynamic_slice_in_dim(py, o, c)
            cused = jax.lax.dynamic_slice_in_dim(used, o, c)
            d = (jnp.abs(cpx[None, :] - ax[:, None])
                 + jnp.abs(cpy[None, :] - ay[:, None]))
            dk, k = _row_min(jnp.where(cused[None, :], _FAR, d))
            better = dk < best_d  # strict: ties keep the earlier chunk's
            return (jnp.where(better, dk, best_d),
                    jnp.where(better, o + k, best_k)), None

        # the backlog scan runs once per task chunk, so outside ``bid``
        (dk, k), _ = jax.lax.scan(
            chunk, (jnp.full(lanes, _FAR, jnp.int32),
                    jnp.zeros(lanes, jnp.int32)),
            jnp.arange(0, nchunks * c, c, dtype=jnp.int32))
        with jax.named_scope("bid"):
            return p + 1, bd.at[lane].set(dk), bk.at[lane].set(k)

    init = (jnp.int32(0), jnp.full(n + 1, _FAR, jnp.int32),
            jnp.zeros(n + 1, jnp.int32))
    _, bd, bk = jax.lax.while_loop(lambda carry: carry[0] < passes, bid,
                                   init)
    return bd[:n], bk[:n]


def _assign(cfg: SolverConfig, s: MapdState, tasks: jnp.ndarray) -> MapdState:
    """Greedy nearest-pickup assignment (ref tswap.rs:123-138), parallelized.

    The reference assigns in agent-id order — a serial chain of N argmins
    over T tasks, O(N*T) sequential work (the round-1 scaling wall).  Here
    every idle agent proposes its nearest unused task at once; contested
    tasks go to the lowest proposing agent id; losers re-propose next round
    over the shrunken pool, until no proposal succeeds.  Each round claims
    >=1 task, so rounds <= min(#idle, #unused) — in practice a handful.
    A fleet wider than _BID_LANES bids only its idle rows
    (:func:`_nearest_idle`); every bid equals the full-width one's.

    Documented approximation (validated for makespan parity like the other
    parallel-ordering divergences, tests/test_solver.py): the result can
    differ from the sequential greedy when agent j (j > i) wins task B in an
    early round while agent i — having lost its first choice A — would have
    claimed B before j in the sequential id-order scan.  The oracle
    (solver/oracle.py) keeps the exact sequential semantics."""
    n = cfg.num_agents
    t = tasks.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def cond(carry):
        return carry[-1]

    def body(carry):
        task_used, goal, phase, agent_task, need, _ = carry
        idle = phase == AgentPhase.IDLE
        if n <= _BID_LANES:
            bd, bk = _nearest_unused(cfg, s.pos, task_used, tasks)
        else:
            bd, bk = _nearest_idle(cfg, s.pos, idle, task_used, tasks)
        want = idle & (bd < _FAR)
        # lowest claimant id per task wins (scratch slot t: no OOB scatter)
        winner = jnp.full(t + 1, n, jnp.int32).at[
            jnp.where(want, bk, t)].min(idx)
        win = want & (winner[bk] == idx)
        claimed = jnp.zeros(t + 1, bool).at[jnp.where(win, bk, t)].set(True)
        return (task_used | claimed[:t],
                jnp.where(win, tasks[bk, 0], goal),
                jnp.where(win, AgentPhase.TO_PICKUP, phase).astype(jnp.int8),
                jnp.where(win, bk, agent_task),
                need | win,
                jnp.any(win))

    init = (s.task_used, s.goal, s.phase, s.agent_task, s.need_replan,
            jnp.bool_(True))
    task_used, goal, phase, agent_task, need, _ = jax.lax.while_loop(
        cond, body, init)
    return s.replace(task_used=task_used, goal=goal, phase=phase,
                     agent_task=agent_task, need_replan=need)


def _replan(cfg: SolverConfig, s: MapdState, free: jnp.ndarray) -> MapdState:
    """Recompute direction-field rows for agents whose goal changed, in
    static-size chunks per round until the set drains.

    Chunking strategy: sweep cost is O(chunk * H * W) per round regardless
    of how few rows are actually dirty, and at steady state only a handful
    of arrivals per step need fields — so the in-step loop uses the NARROW
    ``replan_chunk_small``.  The t=0 burst (all N fields at once) is
    handled by :func:`prime_fields` with the wide ``replan_chunk`` instead.
    Deliberately a single while_loop with one chunk width: a per-round
    ``lax.cond`` between two widths executed at wide-branch cost on the
    chip once fused into the full step program (~1.45 s/step, round 3), and
    a wide-then-narrow pair of while_loops was slower still (~2.7 s/step)
    even with the wide loop at zero iterations — vs 0.19 s/step for this
    shape at the 1k-512 rung."""
    n = cfg.num_agents
    r = min(cfg.replan_chunk_small, n)
    idx = jnp.arange(n, dtype=jnp.int32)

    def cond(carry):
        dirs, need = carry
        return jnp.any(need)

    def body(carry):
        dirs, need = carry
        priority = jnp.where(need, idx, n)
        sel = -jax.lax.top_k(-priority, r)[0]       # r lowest flagged ids
        valid = sel < n
        selc = jnp.clip(sel, 0, n - 1)
        with jax.named_scope("sweep"):
            fields = direction_fields(free, s.goal[selc],
                                      max_rounds=cfg.max_sweep_rounds)
        with jax.named_scope("pack"):
            fields = pack_directions(fields.reshape(r, cfg.num_cells))
        # Invalid lanes clip to agent n-1, whose (goal, slot) pair is still
        # consistent — so their writes are redundant but *correct*, and no
        # out-of-bounds scatter index is ever needed (XLA CPU has been seen
        # wrapping OOB scatter rows instead of dropping them).
        dirs = dirs.at[s.slot[selc]].set(fields)
        cleared = jnp.zeros(n, bool).at[selc].max(valid)
        return dirs, need & ~cleared

    dirs, need = jax.lax.while_loop(cond, body, (s.dirs, s.need_replan))
    return s.replace(dirs=dirs, need_replan=need)


def prime_fields(cfg: SolverConfig, s: MapdState, free: jnp.ndarray) -> MapdState:
    """Compute direction fields for EVERY agent's current goal in wide
    static chunks — the t=0 burst, hoisted out of the per-step replan loop.

    One ``lax.scan`` of ceil(N / replan_chunk) steps (static trip count, no
    data-dependent control flow), each sweeping a (replan_chunk, H, W)
    batch.  Call once after initial task assignment (``prepare_state``);
    afterwards the per-step narrow replan only ever sees incremental goal
    changes.  The tail chunk clips to agent n-1 and recomputes a few rows
    redundantly — their (goal, slot) pairs are consistent, so the extra
    writes are correct."""
    n, r = cfg.num_agents, min(cfg.replan_chunk, cfg.num_agents)
    nchunks = -(-n // r)
    lane = jnp.arange(r, dtype=jnp.int32)

    def chunk(dirs, ci):
        sel = jnp.clip(ci * r + lane, 0, n - 1)
        fields = direction_fields(free, s.goal[sel],
                                  max_rounds=cfg.max_sweep_rounds)
        dirs = dirs.at[s.slot[sel]].set(
            pack_directions(fields.reshape(r, cfg.num_cells)))
        return dirs, None

    with jax.named_scope("prime"):
        dirs, _ = jax.lax.scan(chunk, s.dirs,
                               jnp.arange(nchunks, dtype=jnp.int32))
        return s.replace(dirs=dirs,
                         need_replan=jnp.zeros(n, bool))


def _record(cfg: SolverConfig, s: MapdState) -> MapdState:
    """Path recording (ref tswap.rs:143-158); compile-time no-op (beyond the
    timestep increment) when ``cfg.record_paths`` is off."""
    if not cfg.record_paths:
        return s.replace(t=s.t + 1)
    state = jnp.where(
        s.phase == AgentPhase.IDLE, AgentState.IDLE,
        jnp.where(s.phase == AgentPhase.TO_PICKUP, AgentState.PICKING,
                  jnp.where(s.pos == s.goal, AgentState.DELIVERED,
                            AgentState.CARRYING))).astype(jnp.int8)
    return s.replace(
        paths_pos=jax.lax.dynamic_update_index_in_dim(
            s.paths_pos, s.pos, s.t, axis=0),
        paths_state=jax.lax.dynamic_update_index_in_dim(
            s.paths_state, state, s.t, axis=0),
        t=s.t + 1)


def _commit_pending(cfg: SolverConfig, s: MapdState) -> MapdState:
    """Apply the delayed goal exchanges decided ``swap_commit_delay`` steps
    ago (solver/step.py step_stale): permute (goal, slot, need_replan) by
    ``pend_from`` — exchanged rows stay consistent with exchanged goals —
    then land pushed goals, whose rows ARE stale and flagged for replan.
    Identity pend is a no-op, so calling unconditionally is safe."""
    p = s.pend_from
    goal, slot, need = s.goal[p], s.slot[p], s.need_replan[p]
    pushed = s.pend_push >= 0
    goal = jnp.where(pushed, s.pend_push, goal)
    n = cfg.num_agents
    return s.replace(goal=goal, slot=slot, need_replan=need | pushed,
                     pend_from=jnp.arange(n, dtype=jnp.int32),
                     pend_push=jnp.full(n, -1, jnp.int32))


def _broadcast_view(cfg: SolverConfig, s: MapdState) -> MapdState:
    """Refresh the shared neighbor view for agents whose broadcast is due
    this step — every ``view_refresh_steps`` steps on a per-agent phase
    offset, the decoupled-cadence analog of the reference's per-process
    500 ms position timers (agent.rs:730-789)."""
    n, k = cfg.num_agents, cfg.view_refresh_steps
    phase = jnp.arange(n, dtype=jnp.int32) % k
    due = (s.t + phase) % k == 0
    return s.replace(vpos=jnp.where(due, s.pos, s.vpos),
                     vgoal=jnp.where(due, s.goal, s.vgoal),
                     vstamp=jnp.where(due, s.t, s.vstamp))


def mapd_step(cfg: SolverConfig, s: MapdState, tasks: jnp.ndarray,
              free: jnp.ndarray, replan_fn=None, nh_factory=None) -> MapdState:
    """One full MAPD timestep: (pending-commit) -> transitions ->
    assignment -> replan -> TSWAP step -> record.

    ``replan_fn(cfg, s, free)`` and ``nh_factory(cfg, dirs) -> nh_fn`` let the
    sharded solver (parallel/sharded.py) substitute its distributed field
    machinery while the MAPD sequencing lives in exactly one place.

    Stale mode (cfg.stale_mode): last step's pending goal exchanges commit
    FIRST (they were "on the wire" during the previous step), then the
    normal task lifecycle runs, then the stale-view decision/movement step
    replaces the fresh-atomic kernel.  With ``swap_commit_delay == 0`` the
    exchange instead commits at the END of the same step (decisions were
    still taken on the stale view, but no in-flight window exists).
    """
    stale = cfg.stale_mode
    if stale:
        with jax.named_scope("rules"), jax.named_scope("commit"):
            s = _commit_pending(cfg, s)
    with jax.named_scope("assign"):
        s = _transitions(cfg, s, tasks)
        any_idle = jnp.any((s.phase == AgentPhase.IDLE)
                           & ~jnp.all(s.task_used))
        s = jax.lax.cond(any_idle, lambda s: _assign(cfg, s, tasks),
                         lambda s: s, s)
    with jax.named_scope("replan"):
        s = (replan_fn or _replan)(cfg, s, free)
    if stale:
        # step_stale opens "rules" for its decisions and "move" for the
        # cascade itself
        with jax.named_scope("rules"):
            with jax.named_scope("view"):
                s = _broadcast_view(cfg, s)
            if nh_factory is None:
                nh_fn = lambda sl, po: step_mod.next_hops(cfg, s.dirs, sl, po)
            else:
                nh_fn = nh_factory(cfg, s.dirs)
            visible = (jnp.ones(cfg.num_agents, bool)
                       if cfg.view_ttl_steps is None
                       else (s.t - s.vstamp) <= cfg.view_ttl_steps)
        pos, pend_from, pend_push = step_mod.step_stale(
            cfg, s.pos, s.goal, s.slot, nh_fn, s.vpos, s.vgoal, visible,
            jnp.ones(cfg.num_agents, bool))
        s = s.replace(pos=pos, pend_from=pend_from, pend_push=pend_push)
        if cfg.swap_commit_delay == 0:
            with jax.named_scope("rules"), jax.named_scope("commit"):
                s = _commit_pending(cfg, s)
        with jax.named_scope("record"):
            return _record(cfg, s)
    if nh_factory is None:
        pos, goal, slot = step_parallel(cfg, s.pos, s.goal, s.slot, s.dirs)
    else:
        pos, goal, slot = step_with_next_hops(
            cfg, s.pos, s.goal, s.slot, nh_factory(cfg, s.dirs))
    with jax.named_scope("record"):
        return _record(cfg, s.replace(pos=pos, goal=goal, slot=slot))


def _finished(cfg: SolverConfig, s: MapdState) -> jnp.ndarray:
    """Ref tswap.rs:162-168: all tasks used and all agents idle, or horizon."""
    done = jnp.all(s.task_used) & jnp.all(s.phase == AgentPhase.IDLE)
    return done | (s.t > cfg.max_timesteps)


def validate_starts(grid: Grid, starts_idx) -> None:
    """Host-side input validation shared by every solver front door."""
    starts_np = np.asarray(starts_idx)
    if len(np.unique(starts_np)) != len(starts_np):
        raise ValueError("duplicate start cells: agents must be vertex-disjoint")
    if not grid.free.reshape(-1)[starts_np].all():
        raise ValueError("start cell on an obstacle")


def validate_tasks(grid: Grid, tasks) -> None:
    """Reject pickups/deliveries on obstacles — such tasks would otherwise
    pin their agent on an all-INF field and burn the whole solve horizon."""
    tasks_np = np.asarray(tasks)
    if tasks_np.size and not grid.free.reshape(-1)[tasks_np.reshape(-1)].all():
        raise ValueError("task pickup/delivery cell on an obstacle")


def prepare_state_unprimed(cfg: SolverConfig, starts: jnp.ndarray,
                           tasks: jnp.ndarray
                           ) -> Tuple[MapdState, jnp.ndarray]:
    """:func:`prepare_state` minus the field burst: init + pre-loop
    transitions + first assignment.  Callers that cannot run the burst as
    one fused program (see :func:`host_prime_fields`) start here."""
    if tasks.shape[0] == 0:
        tasks = jnp.zeros((1, 2), jnp.int32)
        s = init_state(cfg, starts, 1)
        s = s.replace(task_used=jnp.ones(1, bool))
    else:
        s = init_state(cfg, starts, tasks.shape[0])
    with jax.named_scope("assign"):
        s = _transitions(cfg, s, tasks)
        s = _assign(cfg, s, tasks)
    return s, tasks


@functools.partial(jax.jit, static_argnums=(0, 1))
def _prime_chunk(cfg: SolverConfig, r: int, free: jnp.ndarray,
                 goals: jnp.ndarray) -> jnp.ndarray:
    f = direction_fields(free, goals, max_rounds=cfg.max_sweep_rounds)
    return pack_directions(f.reshape(r, cfg.num_cells))


# Donating the packed-fields buffer halves peak residency (4 GB instead of
# 8 at the 4096^2 rung, where undonated updates still OOM after the
# superseded-buffer fix).
@functools.partial(jax.jit, donate_argnums=0)
def _prime_update(dirs, rows, fields):
    return dirs.at[rows].set(fields)


def host_prime_fields(cfg: SolverConfig, s: MapdState,
                      free: jnp.ndarray) -> MapdState:
    """The t=0 field burst as a HOST-driven loop of per-chunk device
    programs instead of :func:`prime_fields`'s one fused scan.

    Used at EXTREME-class grids, where one program scanning ~100 sweep
    chunks at (chunk, 4096, 4096) crashed the earlier remote chip worker
    (ROADMAP A4 retries it on the present chip).  The jitted chunk
    programs live at module scope so repeated bursts (e.g.
    bench.py's measure + completion passes) reuse the compiled sweep.
    """
    n, r = cfg.num_agents, min(cfg.replan_chunk, cfg.num_agents)
    nchunks = -(-n // r)
    with trace.span("mapd.host_prime_fields", agents=n, chunks=nchunks):
        for ci in range(nchunks):
            sel = np.clip(np.arange(ci * r, (ci + 1) * r), 0, n - 1)
            sel_j = jnp.asarray(sel, jnp.int32)
            with trace.span("mapd.prime_chunk", chunk=ci):
                fields = _prime_chunk(cfg, r, free, s.goal[sel_j])
                # rebind through s so the superseded dirs reference drops
                # each chunk
                s = s.replace(dirs=_prime_update(s.dirs, s.slot[sel_j],
                                                 fields))
    return s.replace(need_replan=jnp.zeros(cfg.num_agents, bool))


def prepare_state(cfg: SolverConfig, starts: jnp.ndarray, tasks: jnp.ndarray,
                  free: jnp.ndarray) -> Tuple[MapdState, jnp.ndarray]:
    """Initial state ready for stepping: init, first task assignment, and
    the wide-chunk field burst (:func:`prime_fields`).  Returns
    ``(state, tasks)`` with the zero-task case substituted by one pre-used
    dummy task so downstream programs stay shape-total.

    Documented divergence (like the parallel-ordering ones in step.py): an
    agent whose start cell IS its assigned pickup gets its pickup->delivery
    flip from the first ``mapd_step``'s transitions — one step earlier than
    the reference loop (tswap.rs:106-121, where t=0 still records the
    pickup phase) — so makespan can shrink by 1 for such agents and no
    PICKING step is recorded for them.  Collision-freedom is unaffected;
    the makespan-parity suite bounds the effect."""
    s, tasks = prepare_state_unprimed(cfg, starts, tasks)
    return prime_fields(cfg, s, free), tasks


def run_mapd(cfg: SolverConfig, starts: jnp.ndarray, tasks: jnp.ndarray,
             free: jnp.ndarray) -> MapdState:
    """Jittable end-to-end MAPD solve. Returns the final state; makespan is
    ``state.t`` and paths are in ``paths_pos/paths_state[: state.t]``."""
    s, tasks = prepare_state(cfg, starts, tasks, free)

    def cond(s):
        # the done test reads what the step's bookkeeping wrote
        with jax.named_scope("record"):
            return ~_finished(cfg, s)

    def body(s):
        return mapd_step(cfg, s, tasks, free)

    return jax.lax.while_loop(cond, body, s)


_run_mapd_jit = jax.jit(run_mapd, static_argnums=0)


def solve_offline(grid: Grid, starts_idx: np.ndarray, tasks: np.ndarray,
                  cfg: SolverConfig | None = None) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host-facing offline solver (capability of ref tswap_mapd).

    Args:
      grid: the world.
      starts_idx: (N,) flat start cells (distinct).
      tasks: (T, 2) int32 [pickup_idx, delivery_idx].

    Returns:
      (paths_pos (makespan, N), paths_state (makespan, N), makespan).
    """
    if cfg is None:
        cfg = SolverConfig(height=grid.height, width=grid.width,
                           num_agents=len(starts_idx))
    validate_starts(grid, starts_idx)
    validate_tasks(grid, tasks)
    if len(tasks) == 0:
        n = len(starts_idx)
        return (np.zeros((0, n), np.int32), np.zeros((0, n), np.int8), 0)
    with trace.span("mapd.solve_offline", agents=len(starts_idx),
                    tasks=int(len(tasks))):
        final = _run_mapd_jit(cfg, jnp.asarray(starts_idx, jnp.int32),
                              jnp.asarray(tasks, jnp.int32),
                              jnp.asarray(grid.free))
        makespan = int(final.t)  # the fetch that syncs the device
    if not cfg.record_paths:
        n = len(starts_idx)
        return (np.zeros((0, n), np.int32), np.zeros((0, n), np.int8),
                makespan)
    return (np.asarray(final.paths_pos[:makespan]),
            np.asarray(final.paths_state[:makespan]), makespan)
