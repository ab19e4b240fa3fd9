"""Robots that moved per tick of the first replay: those whose fetched
position changed (``harness/drivers/offline_ticks_stale.py``).  Says
whether a faster tick is a fleet that stopped moving.  Silent where the
run counts nothing.  The reader of ``moved.decent`` (by its stem)."""


def read(ctx):
    ticks = ctx.get("fleet", {}).get("moved")
    return sum(ticks) / len(ticks) if ticks else None
