"""Device time of the program's ``move`` phase per traced unit (tick or
solve), in ms: the leaf ops under the ``move`` named scope
(``harness/scopes.py``).  The reader of ``move_ms.tick`` and
``move_ms.solve`` (by their stem)."""

from harness import scopes


def read(ctx):
    return scopes.per_unit_ms(ctx, "move")
