"""The device a process computes on, as JAX reports it.

One description with one spelling, ``{"platform", "kind", "count"}``:
solverd's banner and ``stats_response``, ``bench.py``'s rung records and
``chip_smoke.py``'s last line all carry :func:`device_info`.  JAX is
imported on call, so a parent that must stay off the chip may import
this module.
"""

from __future__ import annotations


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
