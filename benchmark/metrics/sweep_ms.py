"""Device time of the field sweeps inside the replan loop per traced
tick, in ms: the leaf ops under ``replan/sweep`` (``harness/scopes.py``).
The reader of ``sweep_ms.tick`` (by its stem)."""

from harness import scopes


def read(ctx):
    return scopes.per_unit_ms(ctx, "replan", "sweep")
