"""Batched parallel TSWAP step kernel.

A parallel-consistent reformulation of the reference's sequential
``tswap_step`` (src/algorithm/tswap.rs:174-286), per SURVEY §7 hard part 1.
All agents act at once on dense (N,) tensors; conflicts resolve with
deterministic lowest-agent-id priority.  Per-agent A* is gone: each agent's
next hop is one gather from its goal's **direction field** (see
``ops.distance``), and goal exchanges never recompute fields — they permute
the ``slot`` indirection that maps agents to field rows.

Step anatomy (one call = one timestep for all N agents):

1. **Goal-swapping phase**, ``swap_rounds`` rounds of:
   - Rule 3 (ref :197-202): agents blocked by a neighbor parked on its own
     goal swap goals with it.  Multiple claimants on one blocker resolve to
     the lowest agent id; applied as a gather permutation of (goal, slot).
   - Rule 4 (ref :204-249): deadlock cycles in the blocking graph
     ``f(i) = occupant of i's next hop`` are detected exactly up to
     ``cycle_cap`` length by iterated composition, and every cycle rotates
     goals "backward along the chain" simultaneously: goal/slot of ``x`` move
     to ``f(x)`` — again a pure permutation.
2. **Movement phase** (ref :257-285): mutual swaps (adjacent pairs that want
   each other's cells) exchange positions; remaining agents cascade into
   free-or-vacated cells, lowest id winning contested cells.  The cascade
   is defined by fixpoint rounds and resolved without them, along blocker
   chains (:func:`_cascade`).  It preserves vertex-disjointness and never
   lets two agents cross an edge except via a mutual swap.

Documented divergences from the sequential reference (validated empirically
for makespan parity in tests):
- swaps/rotations resolve per parallel round, not interleaved per agent;
- an agent moves at most once per step (the reference's in-pass mutual swap
  can move the partner again later in the same pass, tswap.rs:269-278);
- the movement cascade lets an agent enter a cell vacated this step by ANY
  mover, where the sequential pass only sees vacancies created by
  lower-indexed agents — strictly more progress per step;
- **push extension** (deliberate fix of a reference deadlock): when the
  blocker is parked on the mover's OWN goal (two tasks sharing a delivery
  cell — goals equal, so the reference's Rule-3 swap exchanges identical
  values and no-ops forever, tswap.rs:197-202), the blocker's goal is
  retargeted to the mover's current cell; the next movement phase resolves
  the pair as a mutual position swap.  Pushed goals are served by the
  goal-adjacency shortcut below, so the blocker's (stale) field row is
  never consulted for them.

Next-hop lookups enforce Rule 1 explicitly (at-goal agents never move, even
if their field row is stale) and apply a **goal-adjacency shortcut**: an
agent whose goal is exactly one cell away steps straight to it, bypassing
its direction field.  For field-backed goals both are no-ops (the field
would say the same); together they make pushed/stale-row (goal, slot) pairs
— which Rule-3/4 exchanges may hand around — exact within one extra step
for movers and inert for parked agents.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.ops.distance import (
    apply_direction,
    gather_packed,
)


def next_hops(cfg: SolverConfig, dirs: jnp.ndarray, slot: jnp.ndarray,
              pos: jnp.ndarray) -> jnp.ndarray:
    """Desired next cell per agent: one byte gather from that agent's
    nibble-packed direction field (row ``slot[i]``; see
    ``ops.distance.pack_directions``).  Equals ``pos`` for stay (at
    goal/unreachable)."""
    code = gather_packed(dirs, slot, pos)
    return apply_direction(pos, code, cfg.width)


def _occupancy(cfg: SolverConfig, pos: jnp.ndarray,
               active: jnp.ndarray) -> jnp.ndarray:
    """(HW+1,) int32: agent id at each cell, -1 if empty.  Inactive agents
    scatter to the padded scratch cell and never occupy the grid."""
    n = cfg.num_agents
    return jnp.full(cfg.num_cells + 1, -1, jnp.int32).at[
        jnp.where(active, pos, cfg.num_cells)].set(
        jnp.arange(n, dtype=jnp.int32))


def _blockers(occ, pos, u):
    """Agent occupying each agent's desired next cell (-1 free / no move)."""
    has_move = u != pos
    return jnp.where(has_move, occ[u], -1), has_move


def _within_radius(cfg: SolverConfig, pos, i_idx, j_idx):
    """Manhattan-visibility mask for agent pairs (decentralized mode,
    ref TSWAP_RADIUS=15 at src/bin/decentralized/agent.rs:796-801).
    Centralized mode (visibility_radius=None) sees everyone."""
    if cfg.visibility_radius is None:
        return jnp.ones_like(i_idx, bool)
    w = cfg.width
    a, b = pos[i_idx], pos[j_idx]
    mh = (jnp.abs(a % w - b % w) + jnp.abs(a // w - b // w))
    return mh <= cfg.visibility_radius


def _apply_pair_swaps(goal, slot, sel, partner, n):
    """Permute (goal, slot) by the disjoint transpositions {i <-> partner[i]}
    for selected i.

    Scatters go through a padded scratch slot at index ``n`` instead of
    relying on mode="drop": XLA's CPU backend has been observed to *wrap*
    out-of-bounds scatter rows for some shapes instead of dropping them.
    """
    idx = jnp.arange(n, dtype=jnp.int32)
    p = jnp.arange(n + 1, dtype=jnp.int32)
    p = p.at[jnp.where(sel, idx, n)].set(jnp.where(sel, partner, n))
    p = p.at[jnp.where(sel, partner, n)].set(jnp.where(sel, idx, n))
    p = p[:n]
    return goal[p], slot[p]


def _hops(cfg: SolverConfig, nh_fn, slot, pos, goal, active):
    """Next hops with Rule 1 and the goal-adjacency shortcut explicit.

    Rule 1 (at-goal agents never move, ref tswap.rs:186) is enforced here
    directly instead of relying on the field saying STAY at the goal: a
    pushed agent's field row targets its OLD goal, and without the explicit
    check a parked pushed agent would wander off its goal following the
    stale row.  Together with the adjacency shortcut this bounds any
    stale-row effect to one extra step for moving agents and zero for
    parked ones."""
    u = jnp.where(active, nh_fn(slot, pos), pos)
    w = cfg.width
    mh = jnp.abs(pos % w - goal % w) + jnp.abs(pos // w - goal // w)
    u = jnp.where(active & (mh == 1), goal, u)
    return jnp.where(pos == goal, pos, u)


def _swap_phase_round(cfg: SolverConfig, pos, goal, slot, pushed, nh_fn, occ,
                      active):
    n = cfg.num_agents
    idx = jnp.arange(n, dtype=jnp.int32)

    # ---- Rule 3: swap goals with a blocker parked on its own goal ----
    at_goal = pos == goal
    u = _hops(cfg, nh_fn, slot, pos, goal, active)
    b, has_move = _blockers(occ, pos, u)
    bc = jnp.clip(b, 0, n - 1)
    cand = (has_move & (b >= 0) & at_goal[bc]
            & _within_radius(cfg, pos, idx, bc))
    # lowest claimant id per blocker wins
    winner = jnp.full(n + 1, n, jnp.int32).at[jnp.where(cand, b, n)].min(idx)
    sel = cand & (winner[bc] == idx)
    # blocker parked on the mover's own goal: swapping equal goals no-ops
    # (the reference deadlock) -> push the blocker toward the mover's cell.
    # The pushed pair now wants each other's cells, which Rule 4 would read
    # as a 2-cycle and rotate straight back to self-goals — undoing the push
    # and marking the delivery at the wrong cell — so pushed agents are
    # flagged and excluded from the cycle graph for the rest of the step;
    # the movement phase then resolves the pair as a mutual position swap
    # and the mover PHYSICALLY reaches the contested cell.
    same_goal = goal[bc] == goal
    sel3 = sel & ~same_goal
    push = sel & same_goal
    goal, slot = _apply_pair_swaps(goal, slot, sel3, bc, n)
    ge = jnp.concatenate([goal, jnp.zeros(1, goal.dtype)])
    ge = ge.at[jnp.where(push, bc, n)].set(jnp.where(push, pos, 0))
    goal = ge[:n]
    pe = jnp.concatenate([pushed, jnp.zeros(1, bool)])
    pushed = pe.at[jnp.where(push, bc, n)].set(True)[:n]

    # ---- Rule 4: rotate goals around blocking cycles ----
    at_goal = pos == goal
    u = _hops(cfg, nh_fn, slot, pos, goal, active)
    b, has_move = _blockers(occ, pos, u)
    # blocking-graph successor; n = absorbing sentinel (chain breaks at
    # at-goal agents automatically: they have no move, f = n).  Chain edges
    # are always adjacent pairs, so pairwise visibility never restricts
    # them; the reference's decentralized mode instead requires the WHOLE
    # chain inside the *initiator's* radius (agent.rs:379-448, the
    # radius-15 nearby cache the initiator walks).  Matching that: a cycle
    # rotates iff at least one member sees every member within its own
    # radius (that member is the initiator broadcasting
    # target_rotation_request); all members then rotate consistently.
    # Freshly-pushed agents absorb (f = n): no cycle may pass through them
    # this step (see the push comment above).
    f = jnp.where(has_move & (b >= 0) & ~pushed, b, n)
    f_ext = jnp.concatenate([f, jnp.array([n], jnp.int32)])

    if cfg.visibility_radius is None:
        def cycle_scan(carry, _):
            y, on_cycle = carry
            y = f_ext[y]
            return (y, on_cycle | (y == idx)), None

        (_, on_cycle), _ = jax.lax.scan(
            cycle_scan, (f, jnp.zeros(n, bool)), None,
            length=cfg.cycle_cap)  # global view: everyone is an initiator
    else:
        # One fused walk computes BOTH plain cycle membership and the
        # radius-constrained initiator flag (they share the same y
        # trajectory — round 3 ran them as two separate scan chains, half
        # of the decent-mode scan premium, VERDICT r3 weak #5); a second
        # walk then ORs the initiator flag around each cycle so members
        # rotate all-or-nothing.
        def member_scan(carry, _):
            y, oc, ok, within = carry
            y = f_ext[y]
            within = within & _within_radius(cfg, pos, idx,
                                             jnp.clip(y, 0, n - 1))
            hit = y == idx
            return (y, oc | hit, ok | (hit & within), within), None

        (_, on_cycle_plain, init_ok, _), _ = jax.lax.scan(
            member_scan,
            (f, jnp.zeros(n, bool), jnp.zeros(n, bool), jnp.ones(n, bool)),
            None, length=cfg.cycle_cap)
        init_ext = jnp.concatenate([init_ok, jnp.array([False])])

        def prop_scan(carry, _):
            y, any_ok = carry
            y = f_ext[y]
            return (y, any_ok | init_ext[y]), None

        (_, any_ok), _ = jax.lax.scan(
            prop_scan, (f, init_ok), None, length=cfg.cycle_cap)
        on_cycle = on_cycle_plain & any_ok
    # each cycle member hands its goal to its successor: perm q[f[x]] = x
    # (padded scratch slot n instead of mode="drop"; see _apply_pair_swaps)
    q = jnp.arange(n + 1, dtype=jnp.int32)
    q = q.at[jnp.where(on_cycle, f, n)].set(jnp.where(on_cycle, idx, n))
    q = q[:n]
    goal, slot = goal[q], slot[q]
    return goal, slot, pushed


def _movement_phase(cfg: SolverConfig, pos, goal, slot, nh_fn, occ, active):
    n = cfg.num_agents
    idx = jnp.arange(n, dtype=jnp.int32)
    u = _hops(cfg, nh_fn, slot, pos, goal, active)
    b, has_move = _blockers(occ, pos, u)
    bc = jnp.clip(b, 0, n - 1)

    # mutual position swap (ref :269-278): i and blocker want each other's cells
    mutual = has_move & (b >= 0) & (u[bc] == pos) & (b != idx)
    return _cascade(cfg, u, b, ~has_move | mutual, jnp.where(mutual, u, pos),
                    active)


def _cascade(cfg: SolverConfig, u, b, decided, newpos, active):
    """Positions after the movement cascade, resolved along blocker chains.

    ``decided`` agents have settled where ``newpos`` says (no move, or a
    mutual swap); every other agent wants its next cell ``u``, whose
    occupant at the step's start is ``b`` (-1 if free).  The cascade is
    the fixpoint of rounds in which an undecided agent enters ``u`` once
    no settled agent ends there and ``b`` has settled elsewhere, the
    lowest id first among the claimants of one cell, for at most
    ``max_move_rounds`` rounds.  Both conditions belong to the cell, so
    every undecided agent aiming at ``u`` claims it in the same round:

    - one contest, before anything moves, finds the only agent that can
      ever enter ``u``: its lowest-id undecided claimant, where no
      settled agent ends on ``u``;
    - that agent moves in the first round if ``u`` is free or ``b``
      settled on a move away (a head), and one round after ``b`` moves if
      ``b`` is undecided (a link); otherwise never.  Its round is its
      depth along the links, and it moves iff the links reach a head
      within ``max_move_rounds`` rounds.

    Depths come from pointer doubling: each agent carries the agent its
    links reach and their count, heads link to a sink, and
    ceil(log2(max_move_rounds)) steps of (N,) gathers resolve every depth
    the rounds could reach.  A chain that ends at an agent that cannot
    move, or runs round a ring, never reaches the sink."""
    n, nc = cfg.num_agents, cfg.num_cells
    idx = jnp.arange(n, dtype=jnp.int32)
    # settled agents claim their final cell with -1, which beats every
    # undecided claimant's id (padded scratch cell for inactive lanes
    # instead of mode="drop"; see _apply_pair_swaps)
    cell = jnp.where(decided, jnp.where(active, newpos, nc), u)
    claim = jnp.full(nc + 1, n, jnp.int32).at[cell].min(
        jnp.where(decided, -1, idx))
    first = ~decided & (claim[u] == idx)
    # a decided occupant that stays holds u, so the occupant of a first
    # claimant's cell is gone (a head) unless it is undecided (a link)
    bc = jnp.clip(b, 0, n - 1)
    link = first & (b >= 0) & ~decided[bc]
    # agent n is the sink; agents that never move point at themselves
    nxt = jnp.append(jnp.where(link, bc, jnp.where(first, n, idx)), n)
    dist = jnp.append(jnp.ones(n, jnp.int32), 0)
    for _ in range(max(cfg.max_move_rounds - 1, 0).bit_length()):
        dist = dist + dist[nxt]
        nxt = nxt[nxt]
    # an agent at depth d is dist = d + 1 links from the sink
    moves = (nxt[:n] == n) & (dist[:n] <= cfg.max_move_rounds)
    return jnp.where(moves, u, newpos)


def step_parallel(cfg: SolverConfig, pos: jnp.ndarray, goal: jnp.ndarray,
                  slot: jnp.ndarray, dirs: jnp.ndarray,
                  active: jnp.ndarray | None = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One TSWAP timestep for all agents.

    Args:
      cfg: static solver config.
      pos:  (N,) int32 flat cell per agent (vertex-disjoint).
      goal: (N,) int32 flat goal cell per agent.
      slot: (N,) int32 agent -> direction-field row (a permutation).
      dirs: (N, ceil(H*W/8)) uint32 nibble-packed direction fields
        (ops.distance.pack_directions), row ``slot[i]`` is agent i's field
        (invariant: row slot[i] encodes descent toward goal[i]).

    Returns:
      (pos, goal, slot) after the step; ``dirs`` is never modified (goal
      exchange = slot permutation).
    """
    return step_with_next_hops(
        cfg, pos, goal, slot, lambda sl, po: next_hops(cfg, dirs, sl, po),
        active)


def _within_radius_pts(cfg: SolverConfig, a, b):
    """Manhattan-visibility between explicit cell arrays — the stale-mode
    variant of :func:`_within_radius` where the observed side comes from the
    broadcast view, not the true positions."""
    if cfg.visibility_radius is None:
        return jnp.ones_like(a, bool)
    w = cfg.width
    mh = jnp.abs(a % w - b % w) + jnp.abs(a // w - b // w)
    return mh <= cfg.visibility_radius


def _view_occupancy(cfg: SolverConfig, vpos, visible):
    """(HW+1,) int32 agent id believed to occupy each cell, -1 if believed
    empty.  Unlike true occupancy, stale positions CAN coincide (two
    last-broadcast entries on one cell); the lowest id wins
    deterministically."""
    n = cfg.num_agents
    occ = jnp.full(cfg.num_cells + 1, n, jnp.int32).at[
        jnp.where(visible, vpos, cfg.num_cells)].min(
        jnp.arange(n, dtype=jnp.int32))
    return jnp.where(occ == n, -1, occ)


def step_stale(cfg: SolverConfig, pos, goal, slot, nh_fn, vpos, vgoal,
               visible, active):
    """One decentralized TSWAP timestep under STALE views — the device
    analog of the reference's actual decentralized tick
    (src/bin/decentralized/agent.rs:730-927): each agent takes ONE action
    (Move / WaitForGoalSwap / WaitForRotation / Wait) from its own fresh
    state plus the last-broadcast ``(vpos, vgoal)`` view of its neighbors,
    and goal exchanges do NOT commit here — they are returned as a pending
    permutation (+ push targets) the caller commits ``swap_commit_delay``
    steps later, mirroring the non-atomic wire coordination
    (agent.rs:1041-1107: the peer mutates its goal at request-receipt time,
    the requester at response-receipt time).

    Decisions-vs-physics split (documented divergence from the reference,
    where positions are self-declared and agents can transiently overlap):
    DECISIONS read the stale view, but movement arbitration stays physical
    — the cascade grants a move only into a cell that is actually free or
    vacated, so recorded paths remain vertex-disjoint and the bench
    invariants stay certifiable.  An agent whose believed-free cell is
    actually occupied simply stays (where the reference agent would have
    overlapped); an agent whose believed-occupied cell is actually free
    waits a round it didn't need to.

    Rule-4 chains are walked over a stale blocking graph, like the
    reference initiator walking its nearby cache (agent.rs:379-448): the
    successor of agent j is whoever the VIEW says occupies j's desired
    next cell.  One shared successor function keeps every detected ring
    consistent (the reference gets per-ring consistency because the
    initiator's message defines the participant list, agent.rs:909-917);
    the staleness enters through the view occupancy — rings can thread
    through ghosts of agents that have since moved, rotating goals that
    did not need rotating, exactly the reference pathology.

    Mutual position swaps are disabled: with stale views two agents cannot
    coordinate a simultaneous edge exchange (the reference's decentralized
    mode has no mutual-swap action either — face-offs resolve as 2-cycle
    rotations, agent.rs:907-921).

    Returns ``(newpos, pend_from, pend_push)``: ``pend_from`` is the
    goal-source permutation to commit later (identity where no exchange),
    ``pend_push`` the pushed-goal cell per agent (-1 none).
    """
    with jax.named_scope("rules"):
        occ, u, mutual, blocked, has_move, pend_from, pend_push = \
            _stale_decisions(cfg, pos, goal, slot, nh_fn, vpos, vgoal,
                             visible, active)
    # ---- movement: Move decisions execute against physical occupancy ----
    # Only believed-free moves are attempted (ref Rule 2); every blocked
    # agent's action is some flavor of wait (WaitForGoalSwap /
    # WaitForRotation / Wait), so the mover set is simply the unblocked.
    # (_movement_cascade additionally executes the terminal mutual swap of
    # committed push pairs — the `mutual` mask.)
    movers = has_move & ~blocked
    with jax.named_scope("move"):
        newpos = _movement_cascade(cfg, pos, u, movers, occ, active, mutual)
    return newpos, pend_from, pend_push


def _stale_decisions(cfg: SolverConfig, pos, goal, slot, nh_fn, vpos, vgoal,
                     visible, active):
    """The decision half of :func:`step_stale`: Rule 3/4 on the view.
    Returns ``(occ, u, mutual, blocked, has_move, pend_from, pend_push)``."""
    n = cfg.num_agents
    idx = jnp.arange(n, dtype=jnp.int32)
    occ = _occupancy(cfg, pos, active)          # physical truth
    with jax.named_scope("view"):
        vis = visible & active
        vocc = _view_occupancy(cfg, vpos, vis)

    # own desired next hop: fresh self-knowledge (pos, goal, own field row)
    u = _hops(cfg, nh_fn, slot, pos, goal, active)
    has_move = active & (u != pos)
    with jax.named_scope("view"):
        bv = jnp.where(has_move, vocc[u], -1)
        bv = jnp.where(bv == idx, -1, bv)       # own stale ghost != blocker
        bvc = jnp.clip(bv, 0, n - 1)
        # an out-of-radius occupant was evicted from the cache (ref
        # agent.rs:797): the cell is believed free
        bv = jnp.where((bv >= 0) & _within_radius_pts(cfg, pos, vpos[bvc]),
                       bv, -1)
        bvc = jnp.clip(bv, 0, n - 1)
        blocked = bv >= 0

    # ---- Rule 3 decision on the view: blocker parked (in view) on its
    # (view) goal -> WaitForGoalSwap ----
    parked_v = vpos == vgoal
    cand3 = blocked & parked_v[bvc]
    same_goal = vgoal[bvc] == goal              # push case (shared delivery)
    # pending exchanges must form a permutation, so each agent joins at
    # most ONE pair: grant each blocker its lowest claimant, then resolve
    # claimant-vs-blocker role conflicts by lowest claimant id
    grant = jnp.full(n + 1, n, jnp.int32).at[
        jnp.where(cand3, bvc, n)].min(idx)
    win = cand3 & (grant[bvc] == idx)
    tgt = grant[:n]                             # claimant granted agent j
    keep = win & ((tgt[idx] == n) | (idx < tgt[idx]))
    keep = keep & ~(win[bvc] & (bvc < idx))
    push = keep & same_goal
    sw = keep & ~same_goal

    pend_from = jnp.arange(n + 1, dtype=jnp.int32)
    pend_from = pend_from.at[jnp.where(sw, idx, n)].set(
        jnp.where(sw, bvc, n))
    pend_from = pend_from.at[jnp.where(sw, bvc, n)].set(
        jnp.where(sw, idx, n))
    pend_push = jnp.full(n + 1, -1, jnp.int32).at[
        jnp.where(push, bvc, n)].set(jnp.where(push, pos, -1))[:n]

    # ---- Rule 4 decision on the view graph: deadlock cycles over ONE
    # shared successor function so detected cycles are consistent rings
    # (the reference's rotation is consistent per ring for the same
    # reason: the initiator's message defines the participant list,
    # agent.rs:909-917).  f(j) = the agent j believes occupies j's desired
    # next cell: fresh own move, stale blocker lookup — exactly what j's
    # own decision tick computes.  Pair participants are excluded (their
    # action this step is the swap). ----
    in_pair = jnp.zeros(n + 1, bool).at[
        jnp.where(keep, idx, n)].set(True).at[
        jnp.where(keep, bvc, n)].set(True)[:n]
    # goal-mutual pairs (each holds the other's cell as GOAL — the state a
    # committed push leaves behind) resolve PHYSICALLY as a terminal
    # mutual position swap in the movement phase below; they must not ALSO
    # read as a Rule-4 2-cycle, or the pended rotation undoes the swap one
    # step later and the pair oscillates forever (positions swap, then
    # goals swap back, ad infinitum).
    occ_u = jnp.where(has_move, occ[u], -1)
    ouc = jnp.clip(occ_u, 0, n - 1)
    mutual = (has_move & (occ_u >= 0) & (occ_u != idx)
              & (goal == u) & (goal[ouc] == pos) & (u[ouc] == pos))
    fmask = blocked & ~in_pair & ~in_pair[bvc] & ~mutual & ~mutual[bvc]
    f = jnp.where(fmask, bv, n)
    f_ext = jnp.concatenate([f, jnp.array([n], jnp.int32)])

    # one fused walk for plain membership + radius-checked initiator flag
    # (same trajectory; see _swap_phase_round's member_scan)
    def member_scan(carry, _):
        y, oc, ok, within = carry
        y = f_ext[y]
        within = within & _within_radius_pts(
            cfg, pos, vpos[jnp.clip(y, 0, n - 1)]) & (y < n)
        hit = y == idx
        return (y, oc | hit, ok | (hit & within), within), None

    with jax.named_scope("walk"):
        (_, on_cycle_plain, init_ok, _), _ = jax.lax.scan(
            member_scan,
            (f, jnp.zeros(n, bool), jnp.zeros(n, bool), jnp.ones(n, bool)),
            None, length=cfg.cycle_cap)
        # all-or-nothing per cycle: members rotate iff SOME member's own
        # walk succeeded (that member is the initiator broadcasting the
        # rotation).  The walk reads f^2(j) onward, so f(j) is read first:
        # a ring of cycle_cap + 1 members, the longest found, is then read
        # whole by every member, and rotates as one
        init_ext = jnp.concatenate([init_ok, jnp.array([False])])

        def prop_scan(carry, _):
            y, any_ok = carry
            y = f_ext[y]
            return (y, any_ok | init_ext[y]), None

        (_, any_ok), _ = jax.lax.scan(
            prop_scan, (f, init_ok | init_ext[f]), None,
            length=cfg.cycle_cap)
        on_cycle = on_cycle_plain & any_ok
    # members hand goals backward along the ring, committing with the
    # same latency as swaps (the rotation message arrives next tick)
    pend_from = pend_from.at[jnp.where(on_cycle, f, n)].set(
        jnp.where(on_cycle, idx, n))
    pend_from = pend_from[:n]
    return occ, u, mutual, blocked, has_move, pend_from, pend_push


def _movement_cascade(cfg: SolverConfig, pos, u, want, occ, active, mutual):
    """Physical movement arbitration for stale mode: like
    :func:`_movement_phase` but with an explicit mover mask and, in
    general, NO mutual swaps (see :func:`step_stale`).

    The single exception is the **terminal mutual swap of a goal-mutual
    pair**: two adjacent agents whose goals are each other's cells (the
    state a committed push leaves behind — and the state the atomic path
    resolves with its in-step mutual position swap).  Without it the pair
    would either deadlock (each waiting for the other to vacate) or — if
    Rule 4 reads the face-off as a 2-cycle — rotate the push away and mark
    the delivery at the WRONG cell.  The swap is sanctioned coordination:
    the push's request/response handshake is exactly the wire exchange
    that establishes it (same reasoning as the atomic path's push,
    step.py Rule-3 comment).  ``mutual`` is computed by the caller
    (step_stale), which also excludes these pairs from the Rule-4 cycle
    graph — the same face-off must not both swap positions AND pend a
    rotation, or the two resolutions undo each other forever."""
    b = jnp.where(want & ~mutual, occ[u], -1)   # true occupant of target
    return _cascade(cfg, u, b, mutual | ~want, jnp.where(mutual, u, pos),
                    active)


def step_with_next_hops(cfg: SolverConfig, pos, goal, slot, nh_fn,
                        active=None):
    """Step core parameterized by the next-hop lookup, so the sharded solver
    (parallel/sharded.py) can swap in a distributed field gather while rule
    semantics stay in exactly one place.

    ``active`` masks out padded/parked agent lanes entirely: inactive agents
    never occupy grid cells, never move, and never participate in swaps —
    the device-side mechanism behind fixed-capacity elastic populations
    (SURVEY §7 hard part 4: join/leave is host bookkeeping over a padded
    agent axis).
    """
    if active is None:
        active = jnp.ones(cfg.num_agents, bool)
    with jax.named_scope("rules"):
        occ = _occupancy(cfg, pos, active)

        def round_body(_, gsp):
            goal, slot, pushed = gsp
            return _swap_phase_round(cfg, pos, goal, slot, pushed, nh_fn,
                                     occ, active)

        goal, slot, _ = jax.lax.fori_loop(
            0, cfg.swap_rounds, round_body,
            (goal, slot, jnp.zeros(cfg.num_agents, bool)))
    with jax.named_scope("move"):
        pos = _movement_phase(cfg, pos, goal, slot, nh_fn, occ, active)
    return pos, goal, slot
