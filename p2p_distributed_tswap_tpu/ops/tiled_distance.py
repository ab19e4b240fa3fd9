"""Grid-tile-sharded distance / direction fields (spatial decomposition).

The TPU realization of the reference's proposed-but-never-built geographic
partitioning (``DECENTRALIZED_ISSUES.md:62-96``: split the grid into regions,
agents subscribe to their neighborhood) and SURVEY §7 step 6: for grids whose
field set cannot fit one chip (SCALING.md: the EXTREME rung's 100k x 4096^2
fields are ~840 GB), the H axis is sharded across a device mesh — each device
holds a horizontal band of every field — and the fast-sweeping relaxation
runs as LOCAL sweeps plus a one-row **halo exchange** per round over ICI
(``jax.lax.ppermute`` of the boundary rows, the collective analog of the
reference's region-boundary subscriptions).

Convergence: fast sweeping is a monotone relaxation to a unique fixpoint
(the exact BFS distance).  A round = full sweeps within each band + relaxing
band-boundary rows against the neighbors' adjacent rows; distance
information therefore crosses at least one band boundary per round, so the
fixpoint needs at most (#devices - 1) extra rounds over the single-device
sweep — and each extra round touches only 1/#devices of the grid per device.
The result is bit-identical to the single-device fields
(tests/test_tiled_distance.py).

All functions here run INSIDE ``jax.shard_map``: ``free_local`` /
``dist_local`` are a device's (H_local, W) band, goals are global flat cell
indices, and ``axis_name`` is the mesh axis the H dimension is sharded over.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from p2p_distributed_tswap_tpu.ops.distance import (
    INF,
    _sweep,
    directions_from_distance,
)

TILES_AXIS = "tiles"


def _exchange_boundary_rows(d: jnp.ndarray, axis_name: str):
    """(above, below) halo rows for each band: the last row of the band
    above and the first row of the band below, INF on the edge bands (no
    neighbor; ppermute leaves non-receiving shards with zeros, which must
    not look like distance 0)."""
    n_dev = jax.lax.axis_size(axis_name)
    perm_down = [(i, i + 1) for i in range(n_dev - 1)]  # send towards +H
    perm_up = [(i + 1, i) for i in range(n_dev - 1)]
    above = jax.lax.ppermute(d[:, -1:, :], axis_name, perm_down)
    below = jax.lax.ppermute(d[:, :1, :], axis_name, perm_up)
    shard = jax.lax.axis_index(axis_name)
    above = jnp.where(shard == 0, INF, above)
    below = jnp.where(shard == n_dev - 1, INF, below)
    return above, below


def _halo_relax(d: jnp.ndarray, free_local: jnp.ndarray,
                axis_name: str) -> jnp.ndarray:
    """Relax each band's boundary rows against the neighbors' adjacent rows:
    ``d[:, 0] <- min(d[:, 0], above_neighbor_last_row + 1)`` and vice versa."""
    if jax.lax.axis_size(axis_name) == 1:
        return d
    above, below = _exchange_boundary_rows(d, axis_name)
    d = d.at[:, :1, :].min(jnp.minimum(above + 1, INF))
    d = d.at[:, -1:, :].min(jnp.minimum(below + 1, INF))
    return jnp.where(free_local[None], d, INF)


def tiled_distance_fields(free_local: jnp.ndarray, goals_idx: jnp.ndarray,
                          width: int, axis_name: str = TILES_AXIS,
                          max_rounds: int = 256,
                          fixpoint_axes=None) -> jnp.ndarray:
    """Exact BFS distances on an H-sharded grid.

    Args:
      free_local: (H_local, W) bool — this device's band of the grid.
      goals_idx: (G,) int32 GLOBAL flat cell indices (replicated).
      width: global grid width (== local width).
      axis_name: mesh axis H is sharded over.
      max_rounds: safety cap (fixpoint detection is global via psum).
      fixpoint_axes: mesh axes the round-count fixpoint reduces over;
        defaults to ``axis_name``.  On a multi-axis mesh whose OTHER axes
        run this sweep with different data (e.g. the 2-D agents x tiles
        solver), pass ALL axes: some backends key collectives on a global
        schedule, so every device must execute the same number of
        halo-exchange rounds even across independent sweeps.

    Returns:
      (G, H_local, W) int32 — this device's band of the exact global fields.
    """
    h_local, w = free_local.shape
    assert w == width
    g = goals_idx.shape[0]
    shard = jax.lax.axis_index(axis_name)
    row0 = shard * h_local  # first global row of this band

    cell = (jnp.arange(h_local * w, dtype=jnp.int32).reshape(1, h_local, w)
            + row0 * w)
    d0 = jnp.where(cell == goals_idx.reshape(g, 1, 1), jnp.int32(0), INF)
    d0 = jnp.where(free_local[None], d0, INF)

    xcoord = jnp.arange(w, dtype=jnp.int32).reshape(1, 1, w)
    ycoord = jnp.arange(h_local, dtype=jnp.int32).reshape(1, h_local, 1)
    free_b = free_local  # 2-D shared-mask contract (ops.distance._sweep)

    def one_round(d):
        d = _sweep(d, free_b, axis=2, reverse=False, coord=xcoord)
        d = _sweep(d, free_b, axis=2, reverse=True, coord=-xcoord)
        d = _sweep(d, free_b, axis=1, reverse=False, coord=ycoord)
        d = _sweep(d, free_b, axis=1, reverse=True, coord=-ycoord)
        return _halo_relax(d, free_local, axis_name)

    def cond(state):
        _, prev_changed, i = state
        return prev_changed & (i < max_rounds)

    def body(state):
        d, _, i = state
        nd = one_round(d)
        # global fixpoint: every band must be stable simultaneously
        changed = jax.lax.psum(
            jnp.any(nd != d).astype(jnp.int32),
            fixpoint_axes if fixpoint_axes is not None else axis_name) > 0
        return nd, changed, i + 1

    d, _, _ = jax.lax.while_loop(cond, body,
                                 (d0, jnp.bool_(True), jnp.int32(0)))
    return d


def tiled_direction_fields(free_local: jnp.ndarray, goals_idx: jnp.ndarray,
                           width: int, axis_name: str = TILES_AXIS,
                           max_rounds: int = 256,
                           fixpoint_axes=None) -> jnp.ndarray:
    """(G, H_local, W) uint8 next-hop directions on an H-sharded grid —
    band-boundary cells see the neighbors' adjacent distance rows through
    one more halo exchange, so codes are bit-identical to the single-device
    ``direction_fields``."""
    d = tiled_distance_fields(free_local, goals_idx, width, axis_name,
                              max_rounds, fixpoint_axes)
    return tiled_directions_from_distance(d, free_local, axis_name)


def tiled_directions_from_distance(d: jnp.ndarray, free_local: jnp.ndarray,
                                   axis_name: str = TILES_AXIS
                                   ) -> jnp.ndarray:
    """Direction codes from an already-computed banded distance field
    (the tail of :func:`tiled_direction_fields`, split out so callers
    needing BOTH the distances and the codes — e.g. the mesh solverd's
    dynamic-world sweep, parallel/solver_mesh.py — pay the sweep once)."""
    if jax.lax.axis_size(axis_name) == 1:
        return directions_from_distance(d, free_local)
    above, below = _exchange_boundary_rows(d, axis_name)
    padded = jnp.concatenate([above, d, below], axis=1)  # (G, H_local+2, W)
    free_pad = jnp.concatenate(
        [jnp.zeros((1, free_local.shape[1]), bool), free_local,
         jnp.zeros((1, free_local.shape[1]), bool)], axis=0)
    # directions computed on the padded band; halo rows' free=False keeps
    # their own codes STAY, and they are sliced off anyway
    codes = directions_from_distance(padded, free_pad)
    return codes[:, 1:-1, :]
