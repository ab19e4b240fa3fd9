"""The plain reference: MAPD/TSWAP semantics written out straight.

It imports nothing of the program and takes nothing the program made.
It follows the semantics the program documents (``solver/mapd.py``,
``solver/step.py``, ``ops/distance.py``), which are the reference
planner's (``tswap.rs``) with the program's documented parallel
orderings:

- next hop: descent of the exact BFS distance to the goal, first
  strictly smaller neighbour in the order (dx, dy) = (0,1), (1,0),
  (0,-1), (-1,0); stay at the goal or where nothing descends;
- Rule 1 (an agent on its goal stays), the goal-adjacency shortcut (a
  goal one cell away is stepped to directly);
- per step: arrival transitions, parallel greedy nearest-pickup
  assignment (lowest task index on ties, lowest agent id per task,
  rounds until none wins), field rows recomputed for changed goals,
  ``swap_rounds`` rounds of Rule 3 (with the push for a blocker on the
  mover's own goal) and Rule 4 (cycles up to ``cycle_cap``), then the
  movement phase (mutual swaps, then a cascade, lowest id first).

:class:`Sim` replays a solve exactly, step by step: the batch cell
compares every recorded position and state with it, the tick cells
every agent's position, goal and phase after every replayed tick.  Its
fields come from :func:`bfs` (one goal at a time, on the host) or, for
10k-agent fleets, from :func:`bfs_device` in bulk; every agent is also
checked by :func:`legality`.
"""

from __future__ import annotations

import numpy as np

INF = 1 << 30
DIRS = ((0, 1), (1, 0), (0, -1), (-1, 0))   # (dx, dy); index = code
IDLE, TO_PICKUP, TO_DELIVERY = 0, 1, 2        # phases
S_IDLE, S_PICKING, S_CARRYING, S_DELIVERED = 0, 1, 2, 3
_FAR = 1 << 20


# --------------------------------------------------------------------------
# distances
# --------------------------------------------------------------------------

def bfs(free: np.ndarray, goal: int) -> np.ndarray:
    """(H*W,) int64 BFS distance to ``goal`` (INF where unreachable),
    level by level over index arrays."""
    h, w = free.shape
    flat = free.reshape(-1)
    dist = np.full(h * w, INF, dtype=np.int64)
    if not flat[goal]:
        return dist
    dist[goal] = 0
    front = np.array([goal], dtype=np.int64)
    level = 0
    while front.size:
        level += 1
        x, y = front % w, front // w
        cand = np.concatenate([
            front[x + 1 < w] + 1, front[x > 0] - 1,
            front[y + 1 < h] + w, front[y > 0] - w])
        cand = cand[flat[cand] & (dist[cand] == INF)]
        cand = np.unique(cand)
        dist[cand] = level
        front = cand
    return dist


def bfs_device(free: np.ndarray, goals: np.ndarray, block: int = 128):
    """(G, H*W) int32 BFS distances for many goals, by plain min-plus
    relaxation in ``jax.numpy`` on the default device, ``block`` goals at
    a time.  For the 10k-robot cell, whose ~10k live fields host BFS
    would take minutes to compute."""
    import jax
    import jax.numpy as jnp

    h, w = free.shape
    out = np.empty((len(goals), h * w), dtype=np.int32)
    relax = _relaxer(h, w)
    free_d = jnp.asarray(free)
    for o in range(0, len(goals), block):
        g = np.asarray(goals[o:o + block], dtype=np.int32)
        # padded to a power of two (at least 16): few shapes to compile,
        # little work wasted on a small batch
        b = min(block, max(16, 1 << (len(g) - 1).bit_length()))
        pad = np.full(b, g[0] if len(g) else 0, np.int32)
        pad[:len(g)] = g
        d = relax(free_d, jnp.asarray(pad))
        out[o:o + len(g)] = np.asarray(d).reshape(b, h * w)[:len(g)]
        del d
    return out


_RELAXERS = {}


def _relaxer(h: int, w: int):
    if (h, w) in _RELAXERS:
        return _RELAXERS[(h, w)]
    import jax
    import jax.numpy as jnp

    inf = jnp.int32(INF)

    def shift(d, dy, dx):
        p = jnp.pad(d, ((0, 0), (1, 1), (1, 1)), constant_values=INF)
        return p[:, 1 + dy:1 + dy + h, 1 + dx:1 + dx + w]

    @jax.jit
    def relax(free, goals):
        cell = jnp.arange(h * w, dtype=jnp.int32).reshape(1, h, w)
        d0 = jnp.where((cell == goals[:, None, None]) & free[None], 0, inf)

        def once(d):
            m = jnp.minimum(jnp.minimum(shift(d, 0, 1), shift(d, 0, -1)),
                            jnp.minimum(shift(d, 1, 0), shift(d, -1, 0)))
            return jnp.where(free[None], jnp.minimum(d, m + 1), inf)

        def body(state):
            d, _ = state
            nd = jax.lax.fori_loop(0, 16, lambda _, x: once(x), d)
            return nd, jnp.any(nd != d)

        d, _ = jax.lax.while_loop(lambda s: s[1], body,
                                  (d0, jnp.bool_(True)))
        return d

    _RELAXERS[(h, w)] = relax
    return relax


def hop(dist: np.ndarray, v: int, h: int, w: int) -> int:
    """Next cell from ``v`` down ``dist`` (first strict minimum in DIRS
    order); ``v`` itself at the goal or where nothing descends."""
    dv = dist[v]
    if dv == 0 or dv >= INF:
        return v
    x, y = v % w, v // w
    best, best_val = v, INF
    for dx, dy in DIRS:
        nx, ny = x + dx, y + dy
        if 0 <= nx < w and 0 <= ny < h:
            nv = dist[ny * w + nx]
            if nv < best_val:
                best, best_val = ny * w + nx, nv
    return best if best_val < dv else v


def _manhattan(a, b, w):
    return np.abs(a % w - b % w) + np.abs(a // w - b // w)


# --------------------------------------------------------------------------
# legality (every agent, every tick)
# --------------------------------------------------------------------------

def legality(free: np.ndarray, positions: np.ndarray) -> np.ndarray:
    """Faults per row of a (T, N) position sequence: agents sharing a
    cell, agents on a cell that is not free, moves longer than one cell
    (from the row before)."""
    w = free.shape[1]
    flat = free.reshape(-1)
    bad = np.zeros(positions.shape[0], dtype=np.int64)
    for t in range(positions.shape[0]):
        p = positions[t]
        bad[t] += len(p) - len(np.unique(p))
        bad[t] += int((~flat[p]).sum())
        if t:
            bad[t] += int((_manhattan(p, positions[t - 1], w) > 1).sum())
    return bad


# --------------------------------------------------------------------------
# the whole solve, exactly (small fleets)
# --------------------------------------------------------------------------

# solver settings that only shape the computation (chunk widths, the
# sweep's round cap, path recording) and those the reference models
_SHAPE_ONLY = {"replan_chunk", "replan_chunk_small", "max_sweep_rounds",
               "record_paths", "assign_chunk"}
_MODELLED = {"max_timesteps", "swap_rounds", "cycle_cap", "max_move_rounds"}


def sim_options(solver: dict) -> dict:
    """:class:`Sim` keyword arguments from a configuration's ``solver``
    entry; a setting the reference does not model (the decentralized
    radius and staleness knobs) is refused, not ignored."""
    unknown = set(solver) - _SHAPE_ONLY - _MODELLED
    if unknown:
        raise ValueError(f"the reference does not model {sorted(unknown)}")
    return {k: solver[k] for k in _MODELLED if k in solver}


class Sim:
    """The offline MAPD solve (``run_mapd``'s semantics), step by step.

    Field rows are modelled as the program keeps them: row ``slot[i]``
    was computed for ``row_goal[slot[i]]``, which goal exchanges permute
    with the slot and which a replan refreshes."""

    def __init__(self, free, starts, tasks, max_timesteps=2000,
                 swap_rounds=2, cycle_cap=32, max_move_rounds=64,
                 fields=None):
        self.free = np.asarray(free, dtype=bool)
        self.h, self.w = self.free.shape
        self.hw = self.h * self.w
        self.tasks = np.asarray(tasks, dtype=np.int64)
        self.n = len(starts)
        self.tmax = max_timesteps
        self.swap_rounds, self.cycle_cap = swap_rounds, cycle_cap
        self.max_move_rounds = max_move_rounds
        # distance rows: row _rowof[g] of _rows is the BFS field of goal g;
        # ``fields(goals) -> (G, H*W)`` computes missing ones in bulk
        self._fields = fields or (lambda goals: np.stack(
            [bfs(self.free, int(g)) for g in goals]))
        self._rowof = np.full(self.hw, -1, dtype=np.int64)
        self._rows = np.empty((0, self.hw), dtype=np.int32)
        self._nrows = 0
        n = self.n
        self.pos = np.asarray(starts, dtype=np.int64).copy()
        self.goal = self.pos.copy()
        self.slot = np.arange(n)
        self.phase = np.zeros(n, dtype=np.int64)
        self.agent_task = np.full(n, -1, dtype=np.int64)
        self.task_used = np.zeros(len(self.tasks), dtype=bool)
        self.need = np.ones(n, dtype=bool)
        self.t = 0
        self.paths_pos, self.paths_state = [], []
        # prepare_state: transitions (none: all idle), assignment, and
        # every row computed for its agent's goal
        self._assign()
        self.row_goal = self.goal.copy()
        self._ensure(self.row_goal)
        self.need[:] = False

    # -- fields -------------------------------------------------------
    def _ensure(self, goals) -> None:
        """Distance rows for every goal in ``goals``."""
        goals = np.unique(np.asarray(goals, dtype=np.int64))
        missing = goals[self._rowof[goals] < 0]
        if not len(missing):
            return
        if self._nrows + len(missing) > self.n + max(self.n // 4, 64):
            self._compact(goals)
            missing = goals[self._rowof[goals] < 0]
        need = self._nrows + len(missing)
        if need > len(self._rows):
            grown = np.empty((max(need, 2 * len(self._rows)), self.hw),
                             dtype=np.int32)
            grown[:self._nrows] = self._rows[:self._nrows]
            self._rows = grown
        d = np.asarray(self._fields(missing))
        self._rows[self._nrows:need] = np.minimum(d, INF)
        self._rowof[missing] = np.arange(self._nrows, need)
        self._nrows = need

    def _compact(self, wanted) -> None:
        """Keep only the rows some agent's field row still holds, and
        those of ``wanted``."""
        live = np.union1d(self.row_goal, wanted)
        keep = self._rowof[live]
        live, keep = live[keep >= 0], keep[keep >= 0]
        self._rows[:len(keep)] = self._rows[keep]
        self._rowof[:] = -1
        self._rowof[live] = np.arange(len(live))
        self._nrows = len(live)

    def dist(self, goal: int) -> np.ndarray:
        self._ensure([goal])
        return self._rows[self._rowof[goal]]

    def _next_hops(self, slot, pos):
        """:func:`hop` for every agent at once, down its row's field."""
        h, w = self.h, self.w
        r = self._rowof[self.row_goal[slot]]
        dv = self._rows[r, pos]
        x, y = pos % w, pos // w
        best, best_val = pos.copy(), np.full(len(pos), INF, dtype=np.int64)
        for dx, dy in DIRS:
            nx, ny = x + dx, y + dy
            ok = (nx >= 0) & (nx < w) & (ny >= 0) & (ny < h)
            cell = np.where(ok, ny * w + nx, 0)
            nv = np.where(ok, self._rows[r, cell], INF)
            better = nv < best_val
            best = np.where(better, cell, best)
            best_val = np.where(better, nv, best_val)
        move = (dv != 0) & (dv < INF) & (best_val < dv)
        return np.where(move, best, pos)

    def _hops(self, slot, pos, goal):
        u = self._next_hops(slot, pos)
        u = np.where(_manhattan(pos, goal, self.w) == 1, goal, u)
        return np.where(pos == goal, pos, u)

    # -- task lifecycle -----------------------------------------------
    def _transitions(self):
        arrived = self.pos == self.goal
        tp = arrived & (self.phase == TO_PICKUP)
        td = arrived & (self.phase == TO_DELIVERY)
        task = np.clip(self.agent_task, 0, None)
        self.goal = np.where(tp, self.tasks[task, 1], self.goal)
        self.phase = np.where(tp, TO_DELIVERY,
                              np.where(td, IDLE, self.phase))
        self.agent_task = np.where(td, -1, self.agent_task)
        self.need |= tp

    def _assign(self, block: int = 1024):
        """Rounds of parallel greedy assignment: every idle agent bids for
        its nearest unused pickup (Manhattan, lowest task index on ties),
        each task goes to its lowest bidder; until no bid wins."""
        n, w = self.n, self.w
        px = (self.tasks[:, 0] % w).astype(np.int32)
        py = (self.tasks[:, 0] // w).astype(np.int32)
        while True:
            cand = np.flatnonzero(self.phase == IDLE)
            if not len(cand):
                return
            bk = np.empty(len(cand), dtype=np.int64)
            bd = np.empty(len(cand), dtype=np.int64)
            for o in range(0, len(cand), block):
                c = self.pos[cand[o:o + block]]
                d = (np.abs(px[None, :] - (c % w).astype(np.int32)[:, None])
                     + np.abs(py[None, :] - (c // w).astype(np.int32)[:, None]))
                d[:, self.task_used] = _FAR
                bk[o:o + block] = np.argmin(d, axis=1)
                bd[o:o + block] = d[np.arange(len(c)), bk[o:o + block]]
            want = bd < _FAR
            if not want.any():
                return
            winner = np.full(len(self.tasks), n)
            np.minimum.at(winner, bk[want], cand[want])
            win = want & (winner[bk] == cand)
            if not win.any():
                return
            a, k = cand[win], bk[win]
            self.task_used[k] = True
            self.goal[a] = self.tasks[k, 0]
            self.phase[a] = TO_PICKUP
            self.agent_task[a] = k
            self.need[a] = True

    # -- one TSWAP step -------------------------------------------------
    def _occupancy(self, pos):
        occ = np.full(self.hw + 1, -1, dtype=np.int64)
        occ[pos] = np.arange(self.n)
        return occ

    def _swap_round(self, pos, goal, slot, pushed, occ):
        n = self.n
        idx = np.arange(n)
        # Rule 3, with the push
        at_goal = pos == goal
        u = self._hops(slot, pos, goal)
        has_move = u != pos
        b = np.where(has_move, occ[u], -1)
        bc = np.clip(b, 0, n - 1)
        cand = has_move & (b >= 0) & at_goal[bc]
        winner = np.full(n + 1, n)
        np.minimum.at(winner, np.where(cand, b, n), idx)
        sel = cand & (winner[bc] == idx)
        same = goal[bc] == goal
        sel3, push = sel & ~same, sel & same
        p = np.arange(n)
        p[idx[sel3]] = bc[sel3]
        p[bc[sel3]] = idx[sel3]
        goal, slot = goal[p], slot[p]
        goal = goal.copy()
        goal[bc[push]] = pos[push]
        pushed = pushed.copy()
        pushed[bc[push]] = True
        # Rule 4: rotate goals around blocking cycles
        u = self._hops(slot, pos, goal)
        has_move = u != pos
        b = np.where(has_move, occ[u], -1)
        f = np.where(has_move & (b >= 0) & ~pushed, b, n)
        f_ext = np.concatenate([f, [n]])
        y = f.copy()
        on_cycle = np.zeros(n, dtype=bool)
        for _ in range(self.cycle_cap):
            y = f_ext[y]
            on_cycle |= y == idx
        q = np.arange(n)
        q[f[on_cycle]] = idx[on_cycle]
        return goal[q], slot[q], pushed

    def _movement(self, pos, goal, slot, occ):
        n = self.n
        idx = np.arange(n)
        u = self._hops(slot, pos, goal)
        has_move = u != pos
        b = np.where(has_move, occ[u], -1)
        bc = np.clip(b, 0, n - 1)
        mutual = has_move & (b >= 0) & (u[bc] == pos) & (b != idx)
        newpos = np.where(mutual, u, pos)
        decided = ~has_move | mutual
        for _ in range(self.max_move_rounds):
            occf = np.full(self.hw + 1, -1, dtype=np.int64)
            occf[newpos[decided]] = idx[decided]
            orig_gone = (b < 0) | (decided[bc] & (newpos[bc] != u))
            claimant = ~decided & (occf[u] == -1) & orig_gone
            win = np.full(self.hw + 1, n)
            np.minimum.at(win, u[claimant], idx[claimant])
            mover = claimant & (win[u] == idx)
            decided = decided | mover
            newpos = np.where(mover, u, newpos)
            if not mover.any():
                break
        return newpos

    def _tswap_step(self):
        occ = self._occupancy(self.pos)
        goal, slot = self.goal, self.slot
        pushed = np.zeros(self.n, dtype=bool)
        for _ in range(self.swap_rounds):
            goal, slot, pushed = self._swap_round(self.pos, goal, slot,
                                                  pushed, occ)
        self.pos = self._movement(self.pos, goal, slot, occ)
        self.goal, self.slot = goal, slot

    # -- the MAPD loop -------------------------------------------------
    def finished(self) -> bool:
        return (bool(self.task_used.all())
                and bool((self.phase == IDLE).all())) or self.t > self.tmax

    def step(self):
        self._transitions()
        if (self.phase == IDLE).any() and not self.task_used.all():
            self._assign()
        self._ensure(self.goal[self.need])
        self.row_goal[self.slot[self.need]] = self.goal[self.need]
        self.need[:] = False
        self._tswap_step()
        state = np.where(
            self.phase == IDLE, S_IDLE,
            np.where(self.phase == TO_PICKUP, S_PICKING,
                     np.where(self.pos == self.goal, S_DELIVERED,
                              S_CARRYING)))
        self.paths_pos.append(self.pos.copy())
        self.paths_state.append(state)
        self.t += 1

    def run(self):
        while not self.finished():
            self.step()
        return (self.t, np.array(self.paths_pos).reshape(-1, self.n),
                np.array(self.paths_state).reshape(-1, self.n))
