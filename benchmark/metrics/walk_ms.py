"""Device time of the stale step's Rule-4 ring walk per traced tick, in
ms: the leaf ops under ``rules/walk`` (``harness/scopes.py``), which are
the two ``cycle_cap`` scans of ``solver/step.py`` ``_stale_decisions``.
Silent on a program that names no ``walk`` phase.  The reader of
``walk_ms.decent`` (by its stem)."""

from harness import scopes


def read(ctx):
    if "walk" not in scopes.PHASES:
        return None
    return scopes.per_unit_ms(ctx, "rules", "walk")
