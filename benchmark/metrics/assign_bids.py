"""Bid passes of the assignment per traced tick: how many times each leaf
op under ``assign/bid`` ran (a pass's own ops, which run once per pass of
``solver.mapd._BID_LANES`` idle agents, not once per task chunk), over
the traced ticks.  Splits a change in assignment time into fewer passes
and cheaper passes.  Silent on a program that names no ``bid`` scope, and
on a window in which no pass ran.  The reader of ``assign_bids.tick``."""

from harness import scopes


def read(ctx):
    ev = scopes.events(ctx)
    if not ev or not scopes.scoped(ev):
        return None
    passes = scopes.runs(ev, "assign", "bid")
    return passes / scopes.units(ctx) if passes else None
