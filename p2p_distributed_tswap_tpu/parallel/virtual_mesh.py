"""Virtual CPU mesh bootstrap (shared by tests/conftest.py and
__graft_entry__.dryrun_multichip).

Multi-chip TPU hardware is not available in CI; sharded code runs on
``xla_force_host_platform_device_count=N`` virtual CPU devices, which
exercise the same SPMD partitioner and collectives as a real mesh.  The CPU
client is created lazily by jax, so the flag takes effect as long as it is
written before the first ``jax.devices("cpu")`` call — even if jax itself is
already imported.

This module deliberately imports nothing heavier than ``os``/``re`` at top
level so callers can invoke :func:`force_virtual_cpu_devices` before their
first jax import.
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"


def force_virtual_cpu_devices(n_devices: int) -> None:
    """Ensure ``XLA_FLAGS`` requests at least ``n_devices`` virtual CPU
    devices.  A preset smaller count is raised to ``n_devices``; a preset
    equal-or-larger count is kept.  Must run before jax creates its CPU
    client; a no-op afterwards (jax caches the client)."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --{_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = (
            flags[:m.start()] + f"--{_FLAG}={n_devices}" + flags[m.end():])


def pin_cpu_backend(n_devices: int):
    """Route the current process onto the virtual CPU backend and return its
    devices: force the device count, restrict platform resolution to CPU
    (best effort — harmless if a backend was already chosen), and pin
    ``jax_default_device`` to CPU so no op ever touches a (possibly broken)
    accelerator plugin.

    NOTE: this is terminal for the process's backend selection — after it
    runs, the default device is CPU and ``JAX_PLATFORMS``/``XLA_FLAGS`` stay
    mutated (they also leak to spawned subprocesses).  Intended for dedicated
    dryrun/test processes, not for code sharing a process with real-TPU work.
    """
    force_virtual_cpu_devices(n_devices)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    devices = jax.devices("cpu")  # never query the default backend
    assert len(devices) >= n_devices, (
        f"need {n_devices} devices, have {len(devices)}")
    jax.config.update("jax_default_device", devices[0])
    return devices
