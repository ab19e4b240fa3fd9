"""Device time per phase of the program, from the scope of each op event
of the traced window.

A phase is a ``jax.named_scope`` of the program (``solver.mapd.PHASES``),
and an op's scope is its HLO ``op_name``
(``jit(mapd_step)/replan/while/body/sweep/...``: the named-scope path it
was traced under).  The v5e's op events carry no op_name of their own
(their stats are the device offset, duration and time scale), so
:func:`events` compiles the cell's program again as its driver compiles
it, which the compile cache serves, and takes each op's scope from that
HLO text: an op event's name starts with its instruction's name, and one
program compiles to the same instruction names each time.

An op belongs to a phase when the phase is a whole component of its
scope path, so nested scopes count in their parent (``sweep`` and
``pack`` in ``replan``); a fused op whose op_name joins several paths
with ``;`` belongs to each phase any of them names.  A phase's time is
measured as busy time is (``trace.busy_s``): the union of the intervals
of its ops, a loop of the phase included, so the loop's own control
counts in the phase; averaged over chips.
"""

from __future__ import annotations

import re

from harness import trace

try:
    from p2p_distributed_tswap_tpu.solver.mapd import PHASES
except ImportError:   # a program without named phases
    PHASES = ()


def under(scope: str, *phases) -> bool:
    """True where one of the op's paths holds ``phases`` in that order
    (``under(s, "replan", "sweep")``: a sweep inside the replan)."""
    for path in scope.split(";") if scope else ():
        i = 0
        for c in path.split("/"):
            if i < len(phases) and c == phases[i]:
                i += 1
        if i == len(phases):
            return True
    return False


def op_names(text: str) -> dict:
    """{instruction name: op_name} of a compiled HLO module's text.  An
    instruction without one (a copy or slice the compiler inserted, a
    loop's bookkeeping) takes the op_name of the instruction that runs its
    computation: the ``while``, ``conditional`` or ``call`` it runs in."""
    own, comp_of, caller = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if not m or comp is None:
            continue
        name = m.group(1)
        comp_of[name] = comp
        op = re.search(r'op_name="([^"]*)"', line)
        if op:
            own[name] = op.group(1)
        for callee in re.findall(r"(?:body|condition|calls)=%([\w.\-]+)",
                                 line):
            caller.setdefault(callee, name)
        branches = re.search(r"branch_computations=\{([^}]*)\}", line)
        if branches:
            for callee in re.findall(r"%([\w.\-]+)", branches.group(1)):
                caller.setdefault(callee, name)

    def resolve(name):
        if name in own:
            return own[name]
        up = caller.get(comp_of[name])
        return resolve(up) if up else ""

    return {name: resolve(name) for name in comp_of}


def program_hlo(run) -> str:
    """The compiled HLO text of the program the cell's window ran: its
    driver's ``Program`` built for the cell's instance, as the driver
    builds it.  The tick state is given by its shapes alone (no device
    state is made); without a sharding, as a committed array lowers, so
    the step lowers to the same module as in the run."""
    import jax

    from harness import spec, world
    from harness.solver import solver_config

    kind = run.work["kind"]
    drv = spec.driver(kind)
    free = world.build_map(run.conf["map"])
    first = world.fleet(free, int(run.conf["agents"]),
                        int(run.conf["orders"]),
                        int(run.work["instance_seed"]))
    cfg = solver_config(run)
    if kind == "offline_solves":
        return drv.Program(cfg, free, [first])._solve.as_text()
    prog = drv.Program(cfg, free, *first)
    state, prog.tasks = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       weak_type=x.weak_type),
        prog._prep.out_info)
    prog.compile(state)
    return prog._step.as_text()


def attach(events: dict, names: dict) -> dict:
    """``events`` with each device op's scope as a fourth element, "" for
    an op ``names`` does not know."""
    def scope(e):
        parts = trace.hlo_parts(e[0])
        return names.get(parts[0], "") if parts else ""

    return {"device": {plane: [list(e[:3]) + [scope(e)] for e in evs]
                       for plane, evs in events["device"].items()},
            "host": events["host"]}


def events(ctx):
    """The traced window's events with each op's scope; None where the
    program names no phases or the trace holds no device op.  Made once
    per run and kept in ``ctx``."""
    if "scoped_trace" not in ctx:
        ev = ctx.get("trace")
        if not PHASES or not ev or not any(ev["device"].values()):
            ctx["scoped_trace"] = None
        else:
            ctx["scoped_trace"] = attach(
                ev, op_names(program_hlo(ctx["run"])))
    return ctx["scoped_trace"]


def _union_s(events: dict, test) -> float:
    """Seconds in which an op whose scope passes ``test`` ran, averaged
    over chips; ``test`` is asked once per distinct scope."""
    seen = {}

    def ok(scope):
        if scope not in seen:
            seen[scope] = test(scope)
        return seen[scope]

    planes = [p for p in events["device"].values() if p]
    if not planes:
        return 0.0
    return sum(trace.union_ns((e[1], e[2]) for e in p if ok(e[3]))
               for p in planes) / len(planes) / 1e9


def _named(scope: str) -> bool:
    return any(under(scope, p) for p in PHASES)


def scoped(events: dict) -> bool:
    """Whether any op of the trace falls under a phase."""
    return any(_named(e[3]) for p in events["device"].values()
               for e in p)


def phase_s(events: dict, *phases) -> float:
    """Seconds of device time under ``phases`` (a path, outermost
    first)."""
    return _union_s(events, lambda s: under(s, *phases))


def split_s(events: dict) -> dict:
    """{phase: seconds} for every phase of ``PHASES``, and ``None``: the
    busy seconds under no phase."""
    out = {p: phase_s(events, p) for p in PHASES}
    out[None] = trace.busy_s(events) - _union_s(events, _named)
    return out


def runs(events: dict, *phases) -> int:
    """How many times the most frequent leaf op under ``phases`` ran, per
    chip: the trip count of a loop the phase sits in."""
    count = {}
    for plane, e, parts in trace.leaf_ops(events):
        if under(e[3], *phases):
            key = (plane, parts[0] if parts else e[0])
            count[key] = count.get(key, 0) + 1
    per_plane = {}
    for (plane, _), n in count.items():
        per_plane[plane] = max(per_plane.get(plane, 0), n)
    return sum(per_plane.values()) // max(len(per_plane), 1)


def units(ctx) -> int:
    """The units the traced window ran: the replayed ticks of a tick cell,
    the solves of a batch cell's cycle."""
    work = ctx["run"].work
    return int(work["segment"]["ticks"]) if "segment" in work \
        else int(work["cycle"])


def per_unit_ms(ctx, *phases):
    """Milliseconds of device time under ``phases`` per traced unit; None
    where the trace has no scoped op."""
    ev = events(ctx)
    if not ev or not scoped(ev):
        return None
    return 1e3 * phase_s(ev, *phases) / units(ctx)
