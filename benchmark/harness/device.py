"""The chip a run is on, and its published peaks.

A run that finds no TPU, or fewer chips than its cell asks for, stops
here with exit code 3 and prints no result.  Peaks come from
``peaks.json``, keyed by ``device_kind``; a kind that is not there is an
error, never a default.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


class NoChip(SystemExit):
    def __init__(self, why: str):
        print(f"benchmark: {why}", file=sys.stderr, flush=True)
        super().__init__(3)


def require(chips: int) -> dict:
    """``{platform, kind, count}`` of the TPU this process holds."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}")
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU found (JAX runs on {platform})")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips needed, JAX sees {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def peaks(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{PEAKS.name}")
    return table[kind]


def memory_peak_bytes(count: int) -> int:
    """Peak bytes in use on the fullest of the first ``count`` devices."""
    import jax

    peak = 0
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
