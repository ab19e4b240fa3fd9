"""Device-mesh helpers.

The framework's scale-out axis is the **agent axis** (SURVEY §2: the reference
parallelizes per-agent across OS processes; the TPU-native analog is sharding
the agent/field tensors over a ``jax.sharding.Mesh`` and exchanging the few
bytes of cross-shard state over ICI collectives instead of gossipsub
broadcast).  Direction fields — the memory- and FLOP-heavy state — live
sharded by field row; the small (N,) control vectors stay replicated.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AGENTS_AXIS = "agents"
TILES_AXIS = "tiles"


def _default_devices(n_devices: int | None = None):
    default = jax.config.jax_default_device
    devices = (jax.devices(default.platform) if default is not None
               else jax.devices())
    return devices if n_devices is None else devices[:n_devices]


def agent_tile_mesh(n_agent_shards: int, n_tiles: int,
                    devices=None) -> Mesh:
    """2-D (agents x tiles) mesh: field ROWS shard over the agents axis and
    each row's cells (grid bands) over the tiles axis — the composition
    used for grids/fleets past one chip's field budget (SCALING.md)."""
    if devices is None:
        devices = _default_devices(n_agent_shards * n_tiles)
    assert len(devices) >= n_agent_shards * n_tiles
    return Mesh(
        np.array(devices[:n_agent_shards * n_tiles]).reshape(
            n_agent_shards, n_tiles),
        (AGENTS_AXIS, TILES_AXIS))


def agent_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the agent axis.

    Defaults to all devices of the *default-device* platform when
    ``jax_default_device`` is set (so a CPU-forced test session gets the
    virtual CPU mesh even though a TPU plugin is also registered), else all
    visible devices.
    """
    if devices is None:
        default = jax.config.jax_default_device
        devices = (jax.devices(default.platform) if default is not None
                   else jax.devices())
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (AGENTS_AXIS,))


def field_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (N, H*W) direction fields: rows split over devices."""
    return NamedSharding(mesh, P(AGENTS_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
