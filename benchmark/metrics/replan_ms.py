"""Device time of the program's ``replan`` phase per traced unit (tick or
solve), in ms: the leaf ops under the ``replan`` named scope
(``harness/scopes.py``).  The reader of ``replan_ms.tick`` and
``replan_ms.solve`` (by their stem)."""

from harness import scopes


def read(ctx):
    return scopes.per_unit_ms(ctx, "replan")
