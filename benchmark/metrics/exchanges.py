"""Goal exchanges committed per tick of the first replay: two for a
Rule-3 swap pair, one for a push, one per member of a rotated ring, from
the stale reference's decisions, which equal the program's wherever
``plan_mismatch`` is 0 (``harness/drivers/offline_ticks_stale.py``).
Silent where the run counts nothing.  The reader of
``exchanges.decent`` (by its stem)."""


def read(ctx):
    ticks = ctx.get("fleet", {}).get("exchanges")
    return sum(ticks) / len(ticks) if ticks else None
