"""Device time of the stale step's view per traced tick, in ms: the
leaf ops under ``rules/view`` (``harness/scopes.py``), which are the
broadcast (``solver/mapd.py`` ``_broadcast_view``), the view occupancy
and the blocker lookup on it with the radius eviction
(``solver/step.py`` ``_stale_decisions``).  Silent on a program that
names no ``view`` phase.  The reader of ``view_ms.decent`` (by its
stem)."""

from harness import scopes


def read(ctx):
    if "view" not in scopes.PHASES:
        return None
    return scopes.per_unit_ms(ctx, "rules", "view")
