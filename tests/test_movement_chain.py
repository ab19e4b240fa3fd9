"""The movement cascade resolved along blocker chains equals its rounds.

``solver/step.py`` ``_cascade`` resolves the cascade with one contest per
cell and pointer doubling over blocker links.  The cascade is defined by
fixpoint rounds; ``_rounds`` below is that definition in numpy, the round
loop as the offline reference writes it (``benchmark/harness/reference.py``
``Sim._movement``), with inactive lanes kept out of the occupancy as the
device does.  Both callers are checked, ``_movement_phase`` (the atomic
step) and ``_movement_cascade`` (the stale step), on random floors with
convoys longer than the round cap, contested cells, mutual pairs, blocking
rings and inactive lanes, at caps 1, 2, 3 and 64.  Positions must be equal.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.solver import step

H, W, N = 24, 32, 200
STEPS = ((0, 1), (0, -1), (1, 0), (-1, 0))


def _rounds(hw, u, b, decided, newpos, active, cap):
    """The cascade's fixpoint rounds: an undecided agent enters ``u`` once
    no decided agent ends there and its occupant ``b`` has decided on a
    move away, the lowest id first among one cell's claimants."""
    n = len(u)
    idx = np.arange(n)
    bc = np.clip(b, 0, n - 1)
    for _ in range(cap):
        occf = np.full(hw + 1, -1)
        held = decided & active
        occf[newpos[held]] = idx[held]
        orig_gone = (b < 0) | (decided[bc] & (newpos[bc] != u))
        claimant = ~decided & (occf[u] == -1) & orig_gone
        win = np.full(hw + 1, n)
        np.minimum.at(win, u[claimant], idx[claimant])
        mover = claimant & (win[u] == idx)
        decided = decided | mover
        newpos = np.where(mover, u, newpos)
        if not mover.any():
            break
    return newpos


def _occupancy(pos, active):
    occ = np.full(H * W + 1, -1)
    occ[pos[active]] = np.flatnonzero(active)
    return occ


def _expect_phase(pos, u, active, cap):
    """``_movement_phase``'s round loop: face-offs swap, the rest cascade."""
    idx = np.arange(N)
    occ = _occupancy(pos, active)
    has_move = u != pos
    b = np.where(has_move, occ[u], -1)
    bc = np.clip(b, 0, N - 1)
    mutual = has_move & (b >= 0) & (u[bc] == pos) & (b != idx)
    return _rounds(H * W, u, b, ~has_move | mutual, np.where(mutual, u, pos),
                   active, cap)


def _expect_stale(pos, u, want, mutual, active, cap):
    """``_movement_cascade``'s round loop: marked pairs swap, ``want``
    agents cascade, the rest stay."""
    occ = _occupancy(pos, active)
    b = np.where(want & ~mutual, occ[u], -1)
    return _rounds(H * W, u, b, mutual | ~want, np.where(mutual, u, pos),
                   active, cap)


@functools.lru_cache(maxsize=None)
def _programs(cap):
    cfg = SolverConfig(height=H, width=W, num_agents=N, max_move_rounds=cap)

    def phase(pos, goal, active):
        occ = step._occupancy(cfg, pos, active)
        return step._movement_phase(cfg, pos, goal, jnp.arange(N),
                                    lambda sl, po: po, occ, active)

    def stale(pos, u, want, mutual, active):
        occ = step._occupancy(cfg, pos, active)
        return step._movement_cascade(cfg, pos, u, want, occ, active, mutual)

    return jax.jit(phase), jax.jit(stale)


class _Floor:
    """A random floor on which agents are placed one by one, each with the
    cell it wants next; unplaced lanes stay inactive."""

    def __init__(self, rng, obstacles):
        self.rng = rng
        self.free = rng.random((H, W)) >= obstacles
        self.pos, self.u = [], []

    def cell(self, r, c):
        return r * W + c

    def clear(self, rows):
        """Rows kept free of obstacles for a structure."""
        self.free[rows] = True

    def add(self, at, to):
        self.pos.append(at)
        self.u.append(to)

    def path(self, cells):
        """A convoy along ``cells``: each agent wants the next cell, the
        last cell is left free for the head."""
        for a, b in zip(cells[:-1], cells[1:]):
            self.add(a, b)

    def ring(self, cells):
        """Agents on every cell of a closed ring, each wanting the next."""
        self.path(list(cells) + [cells[0]])

    def scatter(self, count, stay=0.1):
        """Up to ``count`` agents on random free cells of the floor, each
        wanting a random free neighbor (or staying)."""
        used = set(self.pos)
        cells = [c for c in self.rng.permutation(H * W)
                 if self.free.flat[c] and c not in used]
        for c in cells[:count]:
            r, q = divmod(int(c), W)
            nb = [self.cell(r + dr, q + dq) for dr, dq in STEPS
                  if 0 <= r + dr < H and 0 <= q + dq < W
                  and self.free[r + dr, q + dq]]
            go = not nb or self.rng.random() < stay
            self.add(int(c), int(c) if go else int(self.rng.choice(nb)))

    def lanes(self, inactive=0):
        """(pos, u, active) over all N lanes, placed agents in a random lane
        order; ``inactive`` of the rest sit on random cells (placed agents'
        cells among them) wanting random cells."""
        k = len(self.pos)
        assert k + inactive <= N and len(set(self.pos)) == k
        lane = self.rng.permutation(N)
        pos = np.zeros(N, np.int64)
        u = np.zeros(N, np.int64)
        active = np.zeros(N, bool)
        pos[lane[:k]], u[lane[:k]], active[lane[:k]] = self.pos, self.u, True
        rest = lane[k:]
        pos[rest] = self.rng.integers(0, H * W, len(rest))
        u[rest] = pos[rest]
        ghosts = rest[:inactive]
        pos[ghosts[::2]] = self.rng.choice(self.pos, len(ghosts[::2]))
        u[ghosts] = self.rng.integers(0, H * W, len(ghosts))
        return pos, u, active


def _random(rng, density):
    f = _Floor(rng, obstacles=0.15)
    f.scatter(int(density * N))
    return f.lanes()


def _convoy(rng):
    """A serpentine convoy of 100 through four clear rows, longer than the
    largest cap, and a shorter one on the row below; a random fleet
    elsewhere."""
    f = _Floor(rng, obstacles=0.2)
    f.clear(slice(0, 6))
    snake = [f.cell(r, c if r % 2 == 0 else W - 1 - c)
             for r in range(4) for c in range(W)]
    f.path(snake[:101])
    f.path([f.cell(5, c) for c in range(10, 31)])
    f.scatter(60)
    return f.lanes()


def _contest(rng):
    """Cells wanted by 2, 3 and 4 agents, some free, some held by an agent
    that leaves; the claimants have followers of their own."""
    f = _Floor(rng, obstacles=0.2)
    f.clear(slice(0, 9))
    for k, c in enumerate(range(3, W - 3, 6)):
        center = f.cell(4, c)
        sides = [f.cell(3, c), f.cell(5, c), f.cell(4, c - 1),
                 f.cell(4, c + 1)]
        m = 2 + k % 3
        for s in sides[:m]:
            f.add(s, center)
        f.add(f.cell(2, c), f.cell(3, c))        # follows a claimant
        f.add(f.cell(6, c), f.cell(5, c))
        if k % 2 and m < 4:                      # held, and left
            f.path([center, f.cell(4, c + 1), f.cell(4, c + 2)])
        elif k % 2:                              # held for good
            f.add(center, center)
    f.scatter(80)
    return f.lanes()


def _mutual(rng):
    """Face-off pairs; convoys whose heads want a pair's cells, one from
    beside the pair and one from behind a partner."""
    f = _Floor(rng, obstacles=0.2)
    f.clear(slice(0, 8))
    for c in range(2, W - 6, 7):
        a, b = f.cell(3, c), f.cell(3, c + 1)
        f.add(a, b)
        f.add(b, a)
        f.path([f.cell(6, c), f.cell(5, c), f.cell(4, c), a])
        f.path([f.cell(3, c + 4), f.cell(3, c + 3), f.cell(3, c + 2), b])
    f.scatter(80)
    return f.lanes()


def _rings(rng):
    """Blocking rings of 4 and 10 that no rule rotated, with convoys into
    them."""
    f = _Floor(rng, obstacles=0.2)
    f.clear(slice(0, 9))
    for c in range(1, 16, 4):
        f.ring([f.cell(1, c), f.cell(1, c + 1), f.cell(2, c + 1),
                f.cell(2, c)])
        f.path([f.cell(4, c), f.cell(3, c), f.cell(2, c)])
    f.ring([f.cell(r, c) for r, c in
            [(5, 18), (5, 19), (5, 20), (5, 21), (5, 22), (6, 22), (6, 21),
             (6, 20), (6, 19), (6, 18)]])
    f.path([f.cell(8, 20), f.cell(7, 20), f.cell(6, 20)])
    f.scatter(60)
    return f.lanes()


def _inactive(rng):
    f = _Floor(rng, obstacles=0.15)
    f.scatter(120)
    return f.lanes(inactive=60)


SCENES = {
    "sparse": lambda rng: _random(rng, 0.4),
    "dense": lambda rng: _random(rng, 0.95),
    "convoy": _convoy,
    "contest": _contest,
    "mutual": _mutual,
    "rings": _rings,
    "inactive": _inactive,
}


@pytest.mark.parametrize("cap", [1, 2, 3, 64])
@pytest.mark.parametrize("scene", list(SCENES))
@pytest.mark.parametrize("caller", ["phase", "stale"])
def test_chain_resolution_equals_rounds(caller, scene, cap):
    phase, stale = _programs(cap)
    for seed in range(3):
        rng = np.random.default_rng([seed, cap, *scene.encode()])
        pos, u, active = SCENES[scene](rng)
        if caller == "phase":
            # an agent whose goal is one cell away steps to it (step._hops);
            # the others stay on their goals
            want = active & (u != pos)
            goal = np.where(want, u, pos)
            got = phase(jnp.asarray(pos, jnp.int32),
                        jnp.asarray(goal, jnp.int32), jnp.asarray(active))
            expect = _expect_phase(pos, np.where(active, u, pos), active,
                                   cap)
        else:
            idx = np.arange(N)
            occ = _occupancy(pos, active)
            has_move = active & (u != pos)
            b = np.where(has_move, occ[u], -1)
            bc = np.clip(b, 0, N - 1)
            face = has_move & (b >= 0) & (u[bc] == pos)
            # half the face-offs are goal-mutual pairs; a blocked agent
            # waits, whether or not it is in a pair
            mutual = face & (rng.random(N) < 0.5)[np.minimum(idx, bc)]
            want = has_move & (rng.random(N) >= 0.05)
            got = stale(*(jnp.asarray(x, jnp.int32) for x in (pos, u)),
                        jnp.asarray(want), jnp.asarray(mutual),
                        jnp.asarray(active))
            expect = _expect_stale(pos, u, want, mutual, active, cap)
        np.testing.assert_array_equal(np.asarray(got), expect)
