"""Iterations of the replan loop per traced tick: how many times each
leaf op under ``replan/pack`` ran (the packing runs once per chunk of
fields), over the traced ticks.  Splits a change in replan time into
fewer chunks and cheaper chunks.  The reader of ``replan_chunks.tick``."""

from harness import scopes


def read(ctx):
    ev = scopes.events(ctx)
    if not ev or not scopes.scoped(ev):
        return None
    return scopes.runs(ev, "replan", "pack") / scopes.units(ctx)
