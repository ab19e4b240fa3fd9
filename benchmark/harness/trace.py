"""Device trace: capture a window with ``jax.profiler``, reduce it.

Reduction works on plain event lists, so a recorded trace can be checked
without a chip (``tests/data/trace_small.json``):

- ``device``: ``{plane: [[name, start_ns, dur_ns], ...]}``, the per-op
  line of each TPU plane;
- ``host``: ``[[name, start_ns, dur_ns], ...]``, the benchmark's own
  ``bench.*`` annotations (what the host was doing).

On a TPU plane an op event's name is its HLO instruction text
(``%fusion.260 = u32[4,131072,1]{...} fusion(...)``), and container ops
(``while``, ``conditional``, ``call``) span the ops they run, which are
listed too.  Busy time is the union of a plane's op intervals, averaged
over planes (chips); the idle share is ``1 - busy / window``, with the
window timed on the host clock around the traced work.  Per-op totals
count leaf ops only.
"""

from __future__ import annotations

import glob
import os
import re
from contextlib import contextmanager

# On a TPU plane the per-op line; the other lines (modules, steps, ...)
# cover the same time at coarser grain and must not be unioned with it.
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


@contextmanager
def capture(logdir: str):
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(logdir: str) -> dict:
    """Device op events and host annotations from the newest xplane."""
    from jax import profiler

    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane under {logdir}")
    pd = profiler.ProfileData.from_file(files[-1])
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                           for e in line.events)
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host}


def union_ns(intervals) -> int:
    """Length of the union of [start, start + dur) intervals."""
    total, end = 0, None
    for s, d in sorted((int(s), int(d)) for s, d in intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_s(events: dict) -> float:
    """Seconds in which an op ran, averaged over the traced chips."""
    planes = [p for p in events["device"].values() if p]
    if not planes:
        return 0.0
    return sum(union_ns((e[1], e[2]) for e in p)
               for p in planes) / len(planes) / 1e9


CONTAINERS = ("while", "conditional", "call")



def _closing(text: str, i: int) -> int:
    """Index of the parenthesis closing the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j
    return len(text) - 1


def hlo_parts(text: str):
    """(name, result type, opcode, operand text) of an HLO instruction;
    None where ``text`` is not one."""
    m = re.match(r"%?([\w.\-]+) = ", text)
    if not m:
        return None
    rest = text[m.end():]
    if rest.startswith("("):
        j = _closing(rest, 0)
        result, rest = rest[:j + 1], rest[j + 1:].lstrip()
    else:
        result, _, rest = rest.partition(" ")
    i = rest.find("(")
    if i < 0:
        return None
    return m.group(1), result, rest[:i], rest[i:_closing(rest, i) + 1]


def leaf_ops(events: dict):
    """(plane, event, parts) of every op that is not a container."""
    for plane, evs in events["device"].items():
        for e in evs:
            parts = hlo_parts(e[0])
            if parts is None or parts[2] not in CONTAINERS:
                yield plane, e, parts


def top_ops(events: dict, k: int = 10):
    """[[name, seconds]] of the ``k`` leaf ops that took the most device
    time, averaged over chips; a name is the HLO instruction's name and
    opcode."""
    planes = [p for p in events["device"].values() if p]
    agg = {}
    for _, e, parts in leaf_ops(events):
        name = f"{parts[0]} {parts[2]}" if parts else e[0][:80]
        agg[name] = agg.get(name, 0) + e[2]
    n = max(len(planes), 1)
    best = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in best]


def idle_gaps(events: dict, k: int = 10):
    """[[what the host was doing, seconds]]: device idle gaps on the first
    chip, each named by the host annotation that overlaps its middle, the
    ``k`` largest totals."""
    planes = [p for p in events["device"].values() if p]
    if not planes:
        return []
    ivs = sorted((e[1], e[1] + e[2]) for e in planes[0])
    gaps, end = [], ivs[0][1]
    for s, e in ivs[1:]:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    host = sorted(events["host"], key=lambda h: h[1])
    agg = {}
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        what = "no host annotation"
        for name, hs, hd in host:
            if hs <= mid < hs + hd:
                what = name   # innermost: the latest-starting one wins
        agg[what] = agg.get(what, 0) + (ge - gs)
    best = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]
