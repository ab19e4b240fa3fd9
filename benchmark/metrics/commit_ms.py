"""Device time of the stale step's commits per traced tick, in ms: the
leaf ops under ``rules/commit`` (``harness/scopes.py``), which land the
pending goal exchanges (``solver/mapd.py`` ``_commit_pending``, at
either call site).  Silent on a program that names no ``commit``
phase.  The reader of ``commit_ms.decent`` (by its stem)."""

from harness import scopes


def read(ctx):
    if "commit" not in scopes.PHASES:
        return None
    return scopes.per_unit_ms(ctx, "rules", "commit")
