"""Traffic: the world, the fleet and the orders, made from ``--seed``.

A configuration file names its map (``map``) and fleet size; a cell file
names its traffic parameters.  Everything is drawn here, on the host, by
this module's own generator, so a later PR to the program cannot move
it.  The shapes follow the program's conventions (flat row-major cell
index ``y * W + x``, (T, 2) int32 ``[pickup, delivery]``), because those
are the system's inputs.

The warehouse map is the repo's procedural Kiva-style layout (shelf
blocks ``shelf_h x shelf_w`` separated by ``aisle``-wide aisles inside a
``margin``), copied from ``core/grid.py`` ``Grid.warehouse`` so that the
benchmark keeps it if the program's generator changes.
"""

from __future__ import annotations

import numpy as np

# A seed names a world; these offsets split its draws into streams.
_STARTS, _TASKS, _ORDER = 0, 1, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one seed.  Seeds may exceed 32 bits."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), stream])


def warehouse(height: int, width: int, shelf_h: int = 2, shelf_w: int = 8,
              aisle: int = 2, margin: int = 4) -> np.ndarray:
    free = np.ones((height, width), dtype=np.bool_)
    y = margin
    while y + shelf_h <= height - margin:
        x = margin
        while x + shelf_w <= width - margin:
            free[y:y + shelf_h, x:x + shelf_w] = False
            x += shelf_w + aisle
        y += shelf_h + aisle
    return free


def build_map(spec: dict) -> np.ndarray:
    """(H, W) bool, True = free, from a configuration's ``map`` entry."""
    kind = spec["kind"]
    h, w = int(spec["height"]), int(spec["width"])
    if kind == "empty":
        return np.ones((h, w), dtype=np.bool_)
    if kind == "warehouse":
        return warehouse(h, w, **{k: int(spec[k]) for k in
                                  ("shelf_h", "shelf_w", "aisle", "margin")
                                  if k in spec})
    raise ValueError(f"unknown map kind {kind!r}")


def fleet(free: np.ndarray, agents: int, tasks: int, seed: int):
    """``agents`` distinct free start cells and ``tasks`` orders, each a
    (pickup, delivery) pair of distinct free cells, drawn uniformly."""
    cells = np.flatnonzero(free.reshape(-1)).astype(np.int32)
    if agents > len(cells):
        raise ValueError(f"{agents} agents > {len(cells)} free cells")
    starts = rng(seed, _STARTS).choice(cells, size=agents, replace=False)
    r = rng(seed, _TASKS)
    pickup = r.integers(0, len(cells), size=tasks)
    # a delivery distinct from its pickup: shift by 1..len-1 (uniform)
    delivery = (pickup + r.integers(1, len(cells), size=tasks)) % len(cells)
    orders = np.stack([cells[pickup], cells[delivery]], axis=1)
    return starts.astype(np.int32), orders.astype(np.int32)


def order(seed: int, n: int) -> np.ndarray:
    """A permutation of ``range(n)`` from the seed."""
    return rng(seed, _ORDER).permutation(n)
