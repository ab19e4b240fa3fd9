"""JAX's persistent compilation cache, at a place the caller can set.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself,
and :func:`enable` leaves that setting alone.  Otherwise the cache lives
at ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
what a later process must find again.

Program entry points call :func:`enable` at start-up (``solverd``, a
``bench.py`` rung child, each ``chip_smoke.py`` phase).  Nothing calls it
at import, so the tests stay uncached.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for this process; returns its
    directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


class CacheCounter:
    """Persistent-cache hits and misses in this process from its creation
    on, as JAX's monitoring events report them."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses}
