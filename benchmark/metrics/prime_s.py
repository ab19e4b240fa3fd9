"""Seconds of the t=0 field burst: host clock around
``solver.mapd.prepare_state`` (compiled ahead) and its
``block_until_ready``."""


def read(ctx):
    return ctx.get("host_s", {}).get("prime_s")
