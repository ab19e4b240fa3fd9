"""Mesh-sharded solverd execution (ISSUE 13): the live planning plane
spans a device mesh.

Everything the solver daemon keeps device-resident — the direction-field
cache (the dominant buffer: O(cached goals x HW/2) bytes), the flat
fleet lanes, and the multi-tenant [T, L] super-batch — becomes sharded
arrays on a ``jax.sharding.Mesh``, and the step/sweep programs run under
``shard_map``:

- **field ROWS shard over the agents axis** (``parallel/sharded.py``'s
  proven layout): each device holds ``rows / A`` packed rows, so peak
  per-device HBM shrinks ~mesh-size.  The step's only cross-shard
  traffic is the next-hop exchange: the device owning ``slot[i]``'s row
  block contributes agent i's direction code and ONE ``psum`` assembles
  the replicated (N,) vector — bit-identical integer math, O(N) bytes
  per lookup.
- **lane state (pos/goal/slot/active) shards over the agents axis** in
  HBM and is re-replicated at step entry (control flow — occupancy,
  swap rules, the movement cascade — is replicated determinism, exactly
  the ``parallel/sharded.py`` contract).
- **optional grid-tile axis** (``AxT`` specs): the field sweeps run as
  H-banded local sweeps + one-row halo exchanges per round
  (``ops/tiled_distance.py``, bit-identical per its tests); the dirs
  cache itself stays row-sharded only (the tiles axis is a sweep
  throughput/workspace lever, not a cache-residency one).

The solverd paths that consume this module keep their exact wire and
host bookkeeping; sharding is purely an execution/residency lever.  The
exactness contract — mesh solverd produces bit-identical plans, packed
rows, and audit digests to the single-device daemon — is enforced by
tests/test_mesh_solverd.py on the virtual CPU mesh
(``parallel/virtual_mesh.py``).

``parse_mesh_spec`` grammar (JG_SOLVER_MESH / solverd --mesh):
``"4"`` = 4-way agent-axis mesh, ``"2x4"`` = 2 agent shards x 4 grid
tiles, ``"1"``/``"1x1"`` = explicit single-device (callers treat it as
mesh OFF — the flat path).
"""

from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2p_distributed_tswap_tpu.ops.distance import (
    apply_direction,
    direction_fields,
    directions_from_distance,
    distance_fields,
    gather_packed,
    pack_directions,
)
from p2p_distributed_tswap_tpu.ops.tiled_distance import (
    tiled_directions_from_distance,
    tiled_distance_fields,
)
from p2p_distributed_tswap_tpu.parallel.mesh import AGENTS_AXIS, TILES_AXIS
from p2p_distributed_tswap_tpu.solver.step import step_with_next_hops

_SPEC_RE = re.compile(r"^(\d+)(?:x(\d+))?$")


def parse_mesh_spec(spec: str) -> Tuple[int, int]:
    """``"N"`` -> (N, 1); ``"AxT"`` -> (A, T).  Raises ValueError on
    anything else (zero counts included) — a malformed mesh spec must
    fail loudly at startup, never silently serve single-device."""
    m = _SPEC_RE.match(str(spec).strip().lower())
    if m is None:
        raise ValueError(f"bad mesh spec {spec!r} (want N or AxT)")
    a = int(m.group(1))
    t = int(m.group(2)) if m.group(2) is not None else 1
    if a < 1 or t < 1:
        raise ValueError(f"bad mesh spec {spec!r}: counts must be >= 1")
    return a, t


def mesh_spec_from_env(env: Optional[str]) -> Optional[Tuple[int, int]]:
    """JG_SOLVER_MESH value -> (A, T), with unset/empty/1/1x1 -> None
    (the single-device path)."""
    if not env:
        return None
    a, t = parse_mesh_spec(env)
    if a * t == 1:
        return None
    return a, t


def _default_devices(n: int):
    """First ``n`` devices of the default-device platform (a CPU-forced
    test session gets the virtual CPU mesh even with a TPU plugin
    registered) — same resolution rule as parallel.mesh."""
    default = jax.config.jax_default_device
    devices = (jax.devices(default.platform) if default is not None
               else jax.devices())
    if len(devices) < n:
        raise RuntimeError(
            f"mesh needs {n} devices, have {len(devices)} "
            f"(virtual CPU mesh: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            f"creates its CPU client)")
    return devices[:n]


def _local_next_hops(cfg, dirs_local: jnp.ndarray):
    """The distributed ``dirs[slot[i], pos[i]]`` for solverd lanes: slot
    is NOT a permutation (many lanes may share a goal row, rows may be
    unreferenced), so ownership is by row-block — the shard holding
    ``slot[i] // rows_local`` contributes lane i's code, one psum
    assembles all N.  Exact: exactly one shard contributes a nonzero
    int32 per lane."""
    rows_local = dirs_local.shape[0]

    def nh(slot, pos):
        shard = jax.lax.axis_index(AGENTS_AXIS)
        local = (slot // rows_local) == shard
        lrow = jnp.where(local, slot - shard * rows_local, 0)
        vals = gather_packed(dirs_local, lrow, pos)
        contrib = jnp.where(local, vals.astype(jnp.int32), 0)
        codes = jax.lax.psum(contrib, AGENTS_AXIS).astype(jnp.uint8)
        return apply_direction(pos, codes, cfg.width)

    return nh


class SolverMesh:
    """One solverd process's device mesh + the sharded program builders.

    ``n_agent_shards`` (A) splits field rows / lanes; ``n_tiles`` (T)
    optionally bands the sweeps over grid rows.  The mesh is
    (A x T)-shaped even when T == 1 so axis names stay uniform."""

    def __init__(self, n_agent_shards: int, n_tiles: int = 1,
                 devices=None):
        if n_agent_shards < 1 or n_tiles < 1:
            raise ValueError("mesh axes must be >= 1")
        self.n_agent_shards = n_agent_shards
        self.n_tiles = n_tiles
        self.n_devices = n_agent_shards * n_tiles
        if devices is None:
            devices = _default_devices(self.n_devices)
        self.mesh = Mesh(
            np.array(devices[:self.n_devices]).reshape(n_agent_shards,
                                                       n_tiles),
            (AGENTS_AXIS, TILES_AXIS))
        self.row_sharding = NamedSharding(self.mesh, P(AGENTS_AXIS, None))
        self.lane_sharding = NamedSharding(self.mesh, P(AGENTS_AXIS))
        self.slab_sharding = NamedSharding(self.mesh,
                                           P(None, AGENTS_AXIS))
        self.replicated = NamedSharding(self.mesh, P())

    @property
    def shape_str(self) -> str:
        return f"{self.n_agent_shards}x{self.n_tiles}"

    # -- geometry helpers -------------------------------------------------
    def round_lanes(self, n: int) -> int:
        """Next multiple of the agent-shard count (lane capacities must
        divide over the shards; pow2 doubling preserves the property)."""
        a = self.n_agent_shards
        return -(-n // a) * a

    def round_rows(self, rows: int) -> int:
        return self.round_lanes(rows)

    def validate_grid(self, grid) -> None:
        if self.n_tiles > 1 and grid.height % self.n_tiles:
            raise ValueError(
                f"grid height {grid.height} must divide over "
                f"{self.n_tiles} tiles (mesh {self.shape_str})")

    # -- array placement --------------------------------------------------
    def pin_rows(self, arr):
        """Row-shard the (rows, words) dirs cache (rows % A == 0,
        enforced by the callers' round_rows growth)."""
        return jax.device_put(arr, self.row_sharding)

    def pin_lanes(self, arr):
        """Agent-axis-shard a per-lane vector (replicate when the length
        doesn't divide — correctness never depends on the layout)."""
        if arr.shape[0] % self.n_agent_shards:
            return jax.device_put(arr, self.replicated)
        return jax.device_put(arr, self.lane_sharding)

    def pin_slab(self, arr):
        """Lane-axis-shard a [T_cap, L_cap] slab plane."""
        if arr.shape[1] % self.n_agent_shards:
            return jax.device_put(arr, self.replicated)
        return jax.device_put(arr, self.slab_sharding)

    def shard_bytes(self, arrays) -> Dict[int, int]:
        """Per-device resident bytes of ``arrays`` (addressable shards
        only — exact on the virtual CPU mesh and on a single host's
        chips).  Keys are mesh positions 0..n_devices-1, stable across
        runs."""
        order = {d.id: k for k, d in
                 enumerate(self.mesh.devices.reshape(-1))}
        per: Dict[int, int] = {k: 0 for k in range(self.n_devices)}
        for a in arrays:
            if a is None:
                continue
            shards = getattr(a, "addressable_shards", None)
            if shards is None:
                continue
            for s in shards:
                k = order.get(s.device.id)
                if k is not None:
                    per[k] += int(np.prod(s.data.shape)) * s.data.dtype.itemsize
        return per

    # -- sharded programs -------------------------------------------------
    def make_step(self):
        """Jitted ``step(cfg, pos, goal, slot, dirs, active)`` matching
        solver.step.step_parallel's contract, executed under shard_map:
        dirs row-sharded, everything else replicated, the next-hop psum
        the only collective.  Bit-identical to the flat step."""
        mesh = self.mesh

        @functools.partial(jax.jit, static_argnums=0)
        def mesh_step(cfg, pos, goal, slot, dirs, active):
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(), P(), P(), P(AGENTS_AXIS, None), P()),
                out_specs=(P(), P(), P()), check_vma=False)
            def inner(pos, goal, slot, dirs_local, active):
                nh = _local_next_hops(cfg, dirs_local)
                return step_with_next_hops(cfg, pos, goal, slot, nh,
                                           active)

            return inner(pos, goal, slot, dirs, active)

        return mesh_step

    def make_slab_step(self, cfg):
        """The multi-tenant super-batch step under shard_map: one vmap
        over tenant rows INSIDE the mesh program (each row's next-hop
        lookups psum over the shared row-sharded field cache).  Same
        call signature as TenantSlab's flat vstep."""
        mesh = self.mesh

        @jax.jit
        def mesh_vstep(pos, goal, slot, active, dirs):
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(), P(), P(), P(), P(AGENTS_AXIS, None)),
                out_specs=(P(), P(), P()), check_vma=False)
            def inner(pos, goal, slot, active, dirs_local):
                def one(p, g, s, a):
                    nh = _local_next_hops(cfg, dirs_local)
                    return step_with_next_hops(cfg, p, g, s, nh, a)

                return jax.vmap(one)(pos, goal, slot, active)

            return inner(pos, goal, slot, active, dirs)

        return mesh_vstep

    def _pad_goals(self, goals: jnp.ndarray) -> jnp.ndarray:
        g = goals.shape[0]
        pad = -g % self.n_agent_shards
        if pad:
            goals = jnp.concatenate(
                [goals, jnp.broadcast_to(goals[-1:], (pad,))])
        return goals

    def make_fields(self, grid):
        """Sharded twin of PlanService._fields: goal batch split over
        the agents axis (per-goal sweeps are independent, so batching is
        bit-identical), each goal's sweep optionally H-banded over the
        tiles axis with halo exchanges (ops/tiled_distance — also
        bit-identical).  Returns a python wrapper that pads the goal
        batch to a shard multiple and slices the result back."""
        mesh, width = self.mesh, grid.width
        n_tiles = self.n_tiles

        if n_tiles == 1:
            @jax.jit
            def fields_sharded(free, goals):
                @functools.partial(
                    jax.shard_map, mesh=mesh,
                    in_specs=(P(), P(AGENTS_AXIS)),
                    out_specs=P(AGENTS_AXIS, None), check_vma=False)
                def inner(free, goals_local):
                    d = direction_fields(free, goals_local)
                    return pack_directions(
                        d.reshape(goals_local.shape[0], -1))

                return inner(free, goals)
        else:
            @jax.jit
            def fields_sharded(free, goals):
                @functools.partial(
                    jax.shard_map, mesh=mesh,
                    in_specs=(P(TILES_AXIS, None), P(AGENTS_AXIS)),
                    out_specs=P(AGENTS_AXIS, TILES_AXIS, None),
                    check_vma=False)
                def inner(free_local, goals_local):
                    # uniform collective schedule across agent blocks:
                    # they sweep different goal batches, so the halo /
                    # fixpoint collectives must line up mesh-wide
                    d = tiled_distance_fields(
                        free_local, goals_local, width,
                        axis_name=TILES_AXIS,
                        fixpoint_axes=(AGENTS_AXIS, TILES_AXIS))
                    return tiled_directions_from_distance(
                        d, free_local, axis_name=TILES_AXIS)

                codes = inner(free, goals)          # (G, H, W) global
                return pack_directions(
                    codes.reshape(goals.shape[0], -1))

        def wrapper(free, goals):
            g = goals.shape[0]
            return fields_sharded(free, self._pad_goals(goals))[:g]

        return wrapper

    def make_fields_dist(self, grid):
        """Sharded twin of PlanService._fields_dist (dynamic-world
        variant): packed rows plus the raw distance/direction fields the
        host repair mirrors start from."""
        mesh, width = self.mesh, grid.width
        n_tiles = self.n_tiles

        if n_tiles == 1:
            @jax.jit
            def fd_sharded(free, goals):
                @functools.partial(
                    jax.shard_map, mesh=mesh,
                    in_specs=(P(), P(AGENTS_AXIS)),
                    out_specs=(P(AGENTS_AXIS, None),
                               P(AGENTS_AXIS, None, None),
                               P(AGENTS_AXIS, None, None)),
                    check_vma=False)
                def inner(free, goals_local):
                    d = distance_fields(free, goals_local)
                    dirs = directions_from_distance(d, free)
                    return (pack_directions(
                        dirs.reshape(goals_local.shape[0], -1)), d, dirs)

                return inner(free, goals)
        else:
            @jax.jit
            def fd_sharded(free, goals):
                @functools.partial(
                    jax.shard_map, mesh=mesh,
                    in_specs=(P(TILES_AXIS, None), P(AGENTS_AXIS)),
                    out_specs=(P(AGENTS_AXIS, TILES_AXIS, None),
                               P(AGENTS_AXIS, TILES_AXIS, None)),
                    check_vma=False)
                def inner(free_local, goals_local):
                    d = tiled_distance_fields(
                        free_local, goals_local, width,
                        axis_name=TILES_AXIS,
                        fixpoint_axes=(AGENTS_AXIS, TILES_AXIS))
                    codes = tiled_directions_from_distance(
                        d, free_local, axis_name=TILES_AXIS)
                    return d, codes

                d, dirs = inner(free, goals)        # global (G, H, W)
                return (pack_directions(
                    dirs.reshape(goals.shape[0], -1)), d, dirs)

        def wrapper(free, goals):
            g = goals.shape[0]
            packed, d, dirs = fd_sharded(free, self._pad_goals(goals))
            return packed[:g], d[:g], dirs[:g]

        return wrapper
