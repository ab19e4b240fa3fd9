"""The plain reference of the decentralized fleet: TSWAP decided on stale
neighbour views, goal exchanges committed a decision later.

It imports nothing of the program.  It restates the semantics the
program documents for its stale step (``solver/step.py`` ``step_stale``,
``solver/mapd.py`` ``_commit_pending`` and ``_broadcast_view``), which
model the reference's decentralized agent
(``src/bin/decentralized/agent.rs``): one decision per agent per
500-ms tick against a radius-15 cache of its neighbours' broadcast
positions and goals, and goal swaps and rotations exchanged as messages.
A step, in order:

1. the exchanges decided one step earlier commit: goals and field rows
   move by the pending permutation, then pushed goals land (their rows
   are refreshed before they are read);
2. arrival transitions and assignment, as :class:`reference.Sim`;
3. field rows refreshed for changed goals;
4. agent ``i`` broadcasts its position and goal when ``(t + i) % k == 0``
   (``k`` = ``view_refresh_steps``);
5. every agent decides on its own fresh position, goal and row, and on
   the view of the others: Rule 3 pairs, pushes and Rule-4 rings become
   pending exchanges;
6. believed-free moves execute against the true occupancy;
7. with ``swap_commit_delay`` 0 the pending exchanges commit at once.

Departures from ``agent.rs``, each the program's documented one:

- the cache holds every broadcast robot within the radius; the cap of 60
  peers (``:800-836``) is not modelled, and nothing ages out (the 10-s
  TTL, ``:156-167``, is refused);
- broadcasts are one per ``k`` steps on the phase ``i mod k``: the
  per-process 500-ms timers (``:730-789``) with a fixed offset;
- a request and its response take one step together: the peer's goal
  (request receipt, ``:1041-1072``) and the requester's (response
  receipt, ``:1075-1087``) change at the same commit;
- a rotation rotates a whole ring or none of it, and it goes ahead when
  some member sees every member within its radius (the initiator,
  ``:379-448, 907-921``);
- movement stays physical: a believed-free cell that is truly occupied
  is not entered, and two robots that each hold the other's cell as
  goal (the state a committed push leaves) trade places in one step;
  ``agent.rs`` has no mutual move and lets positions overlap;
- the assignment is the program's greedy nearest-pickup rule, where the
  decentralized manager deals orders round-robin.
"""

from __future__ import annotations

import numpy as np

from harness import reference

# settings of the decentralized step this reference models, beside
# those reference.sim_options accepts
_STALE = {"visibility_radius", "view_refresh_steps", "swap_commit_delay"}


def sim_options(solver: dict) -> dict:
    """:class:`StaleSim` keyword arguments from a configuration's
    ``solver`` entry.  A view TTL is refused (not modelled), and so are
    knobs that leave the program on its centralized or fresh-view step."""
    if solver.get("view_ttl_steps") is not None:
        raise ValueError("the stale reference does not model view_ttl_steps")
    opts = reference.sim_options(
        {k: v for k, v in solver.items() if k not in _STALE | {
            "view_ttl_steps"}})
    radius = solver.get("visibility_radius")
    refresh = int(solver.get("view_refresh_steps", 1))
    delay = int(solver.get("swap_commit_delay", 0))
    if radius is None or (refresh <= 1 and delay == 0):
        raise ValueError("not the stale decentralized step: it needs a "
                         "visibility_radius and a view refresh above 1 "
                         "or a commit delay")
    if delay not in (0, 1) or refresh < 1:
        raise ValueError(f"refresh {refresh}, delay {delay}: out of range")
    return dict(opts, radius=int(radius), refresh=refresh, delay=delay)


class StaleSim(reference.Sim):
    """The offline MAPD solve on the decentralized stale step, step by
    step.  ``exchanges`` holds, per step, the goal exchanges committed in
    it: two for a swap pair, one for a push, one per ring member."""

    def __init__(self, free, starts, tasks, radius=15, refresh=2, delay=1,
                 **kw):
        self.radius, self.refresh, self.delay = radius, refresh, delay
        super().__init__(free, starts, tasks, **kw)
        # every robot's first broadcast is its start cell, as its goal
        self.vpos = np.asarray(starts, dtype=np.int64).copy()
        self.vgoal = self.vpos.copy()
        self.pend_from = np.arange(self.n)
        self.pend_push = np.full(self.n, -1, dtype=np.int64)
        self.exchanges = []
        self._committed = 0

    def _near(self, a, b):
        return reference._manhattan(a, b, self.w) <= self.radius

    # -- commits ---------------------------------------------------------
    def _commit(self):
        """Land the pending exchanges: agent i takes the goal (and row) of
        agent ``pend_from[i]``, then pushed goals replace theirs."""
        p, push = self.pend_from, self.pend_push >= 0
        self._committed += int((p != np.arange(self.n)).sum() + push.sum())
        self.goal, self.slot, self.need = (self.goal[p], self.slot[p],
                                           self.need[p])
        self.goal = np.where(push, self.pend_push, self.goal)
        self.need = self.need | push
        self.pend_from = np.arange(self.n)
        self.pend_push = np.full(self.n, -1, dtype=np.int64)

    def step(self):
        self._committed = 0
        self._commit()
        super().step()
        self.exchanges.append(self._committed)

    # -- the decentralized TSWAP step --------------------------------------
    def _tswap_step(self):
        n, idx = self.n, np.arange(self.n)
        due = (self.t + idx) % self.refresh == 0
        self.vpos = np.where(due, self.pos, self.vpos)
        self.vgoal = np.where(due, self.goal, self.vgoal)
        pos, goal = self.pos, self.goal

        u = self._hops(self.slot, pos, goal)
        has_move = u != pos
        # who the view says stands on the next cell: the lowest id among
        # the broadcasts there; never the agent's own old broadcast, and
        # nobody beyond the radius (evicted from the cache)
        seen = np.full(self.hw, n)
        np.minimum.at(seen, self.vpos, idx)
        blocker = np.where(has_move, seen[u], n)
        blocker = np.where(blocker == idx, n, blocker)
        held = blocker < n
        held[held] = self._near(pos[held], self.vpos[blocker[held]])
        blocker = np.where(held, blocker, -1)

        pairs = self._pairs(blocker)
        mutual = self._goal_mutual(pos, goal, u, has_move)
        rings = self._rings(blocker, pairs, mutual)

        # Rule 3: a swap pair trades goals, a push retargets the blocker
        # to the claimant's cell
        for c in np.flatnonzero(pairs):
            b = blocker[c]
            if self.vgoal[b] == goal[c]:
                self.pend_push[b] = pos[c]
            else:
                self.pend_from[c], self.pend_from[b] = b, c
        # Rule 4: each ring member's goal passes to the agent it waits on
        for x in np.flatnonzero(rings):
            self.pend_from[blocker[x]] = x

        self.pos = self._cascade(pos, u, has_move & ~held, mutual)
        if self.delay == 0:
            self._commit()

    def _pairs(self, blocker):
        """Rule 3 on the view: claimant c waits on a blocker the view shows
        parked on its goal.  Each blocker goes to its lowest claimant;
        then a pair is kept only if its claimant's id is below that of
        every other granted pair that shares an agent with it (an agent
        exchanges with one partner a step).  (n,) bool over claimants."""
        n = self.n
        bc = np.clip(blocker, 0, n - 1)
        parked = self.vpos == self.vgoal
        claim = (blocker >= 0) & parked[bc]
        lowest = np.full(n, n)
        np.minimum.at(lowest, bc[claim], np.flatnonzero(claim))
        granted = claim & (lowest[bc] == np.arange(n))
        keep = granted.copy()
        for c in np.flatnonzero(granted):
            rivals = []
            if lowest[c] < n:                 # c is itself a granted blocker
                rivals.append(lowest[c])
            if granted[blocker[c]]:           # c's blocker claims another
                rivals.append(blocker[c])
            keep[c] = all(c < r for r in rivals)
        return keep

    def _goal_mutual(self, pos, goal, u, has_move):
        """Adjacent agents that each want the other's cell and hold it as
        their goal: the pair a committed push leaves, which trades places
        (true positions, not the view)."""
        occ = self._occupancy(pos)[:self.hw]
        other = np.where(has_move, occ[u], -1)
        oc = np.clip(other, 0, self.n - 1)
        return (has_move & (other >= 0) & (other != np.arange(self.n))
                & (goal == u) & (goal[oc] == pos) & (u[oc] == pos))

    def _rings(self, blocker, pairs, mutual):
        """Rule 4 on the view's waits-for graph, without agents in a Rule-3
        pair or a goal-mutual pair: rings of at most ``cycle_cap + 1``
        agents (the length the centralized rule finds).  A ring rotates
        iff one of its members sees every member's broadcast position
        within the radius of its own position.  (n,) bool."""
        n = self.n
        in_pair = pairs.copy()
        in_pair[blocker[pairs]] = True
        bc = np.clip(blocker, 0, n - 1)
        succ = np.where((blocker >= 0) & ~in_pair & ~in_pair[bc] & ~mutual
                        & ~mutual[bc], blocker, -1)
        member = np.zeros(n, dtype=bool)
        sees_all = np.zeros(n, dtype=bool)
        for i in np.flatnonzero(succ >= 0):
            walk, y = [], succ[i]
            while y >= 0 and y != i and len(walk) < self.cycle_cap:
                walk.append(y)
                y = succ[y]
            if y == i:
                ring = walk + [i]
                member[i] = True
                sees_all[i] = bool(self._near(
                    np.full(len(ring), self.pos[i]), self.vpos[ring]).all())
        rotate = np.zeros(n, dtype=bool)
        for i in np.flatnonzero(member & ~rotate):
            ring, y = [i], succ[i]
            while y != i:
                ring.append(y)
                y = succ[y]
            rotate[ring] = sees_all[ring].any()
        return rotate

    def _cascade(self, pos, u, movers, mutual):
        """Physical moves: a goal-mutual pair trades cells; a mover enters
        its next cell once nobody has settled there and its occupant has
        settled elsewhere, the lowest id first among claimants of one
        cell; rounds until nobody moves (at most ``max_move_rounds``)."""
        n, idx = self.n, np.arange(self.n)
        occ = self._occupancy(pos)
        ahead = np.where(movers & ~mutual, occ[u], -1)
        ac = np.clip(ahead, 0, n - 1)
        settled = mutual | ~movers
        new = np.where(mutual, u, pos)
        for _ in range(self.max_move_rounds):
            at = np.full(self.hw + 1, -1)
            at[new[settled]] = idx[settled]
            gone = (ahead < 0) | (settled[ac] & (new[ac] != u))
            claim = ~settled & (at[u] == -1) & gone
            first = np.full(self.hw + 1, n)
            np.minimum.at(first, u[claim], idx[claim])
            go = claim & (first[u] == idx)
            if not go.any():
                break
            settled = settled | go
            new = np.where(go, u, new)
        return new
