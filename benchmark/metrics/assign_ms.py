"""Device time of the program's ``assign`` phase per traced unit (tick or
solve), in ms: the leaf ops under the ``assign`` named scope
(``harness/scopes.py``).  The reader of ``assign_ms.tick`` and
``assign_ms.solve`` (by their stem)."""

from harness import scopes


def read(ctx):
    return scopes.per_unit_ms(ctx, "assign")
