"""Device time of the program's ``rules`` phase per traced unit (tick or
solve), in ms: the leaf ops under the ``rules`` named scope
(``harness/scopes.py``).  The reader of ``rules_ms.tick`` and
``rules_ms.solve`` (by their stem)."""

from harness import scopes


def read(ctx):
    return scopes.per_unit_ms(ctx, "rules")
