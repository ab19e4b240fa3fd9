"""Device idle share of the traced window, in percent:
1 - (union of device op intervals) / (host-timed window).  The reader of
``device_idle.tick`` and ``device_idle.solve`` (by their stem)."""

from harness import trace


def read(ctx):
    ev = ctx.get("trace")
    if not ev or not any(ev["device"].values()) or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(ev) / ctx["window_s"])
