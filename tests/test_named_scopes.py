"""The step's phases are named on the device: every op of the compiled
step carries the ``jax.named_scope`` path it was traced under in its HLO
``op_name``, which is how a device trace sums op time per phase
(``solver.mapd.PHASES``).  Compiled on the CPU at small shapes; scopes are
metadata only, so what holds here holds for the chip's compiler too."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.solver import mapd

# a warehouse floor like the LoRR one, at a side that is not lane-aligned
# (the XLA sweep), and the reference fleet's empty square
WAREHOUSE = dict(height=28, width=100, num_agents=440, replan_chunk=16,
                 replan_chunk_small=4, max_timesteps=400, record_paths=False)
EMPTY = dict(height=20, width=20, num_agents=8, replan_chunk=8,
             max_timesteps=400, record_paths=True)
TOP = ("assign", "replan", "rules", "move", "record")
# a nested phase and the phase it runs in
INNER = {"sweep": "replan", "pack": "replan", "bid": "assign",
         "commit": "rules", "view": "rules", "walk": "rules"}
# the decentralized stale step (ROADMAP A3), its commit at the next step
# or at once
STALE = dict(visibility_radius=15, view_refresh_steps=2, swap_commit_delay=1)
STALE_NOW = dict(STALE, swap_commit_delay=0)
# the phases only the stale step has, and the functions each wraps, as the
# compiled module's stack frames name them
STALE_PHASES = {
    "commit": ("_commit_pending",),
    "view": ("_broadcast_view", "_view_occupancy"),
    "walk": ("_stale_decisions.<locals>.member_scan",
             "_stale_decisions.<locals>.prop_scan")}


def _args(cfg, num_tasks):
    n = cfg.num_agents
    return (jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((num_tasks, 2), jnp.int32),
            jax.ShapeDtypeStruct((cfg.height, cfg.width), jnp.bool_))


def _step_text(cfg, num_tasks) -> str:
    args = _args(cfg, num_tasks)
    state, tasks = jax.eval_shape(
        lambda *a: mapd.prepare_state(cfg, *a), *args)
    return jax.jit(mapd.mapd_step, static_argnums=0).lower(
        cfg, state, tasks, args[2]).compile().as_text()


def _solve_text(cfg, num_tasks) -> str:
    return mapd._run_mapd_jit.lower(cfg, *_args(cfg, num_tasks)).compile() \
        .as_text()


def _computations(text: str) -> dict:
    """{computation name: [instruction lines]} of an HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.strip().startswith(("%", "ROOT %")):
            cur.append(line.strip())
    return comps


def _op_name(line: str):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else None


def _paths(text: str):
    """The scope path (components) of every op_name in the module; a fused
    op joins the op_names it merged with ``;``."""
    for line in text.splitlines():
        op = _op_name(line)
        if op:
            yield from (p.split("/") for p in op.split(";"))


def _loop_ops(comps: dict, body: str):
    """Instruction lines the loop body runs: its own and those of the
    loops, branches, calls and fusions inside it (not the reducers that
    ``to_apply`` names, which run inside their op)."""
    seen, todo = set(), [body]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:body|condition|calls)=%([\w.\-]+)", line)
            m = re.search(r"branch_computations=\{([^}]*)\}", line)
            if m:
                todo += re.findall(r"%([\w.\-]+)", m.group(1))
            yield line


def _check_phases(text: str, expected) -> None:
    paths = list(_paths(text))
    named = {c for p in paths for c in p}
    assert set(expected) <= named, set(expected) - named
    for p in paths:
        for inner, outer in INNER.items():
            if inner in p:
                assert outer in p[:p.index(inner)], p


def _check_replan_body(text: str) -> None:
    comps = _computations(text)
    bodies = [re.search(r"body=%([\w.\-]+)", line).group(1)
              for lines in comps.values() for line in lines
              if " while(" in line
              and (_op_name(line) or "").endswith("replan/while")]
    assert len(bodies) == 1
    checked = 0
    for line in _loop_ops(comps, bodies[0]):
        op = _op_name(line)
        if op is None or re.search(r" (parameter|constant)\(", line):
            continue   # no metadata: the op runs inside the replan loop
        for p in op.split(";"):
            assert "replan" in p.split("/"), line
        checked += 1
    assert checked > 0


def test_phases_are_the_scope_names():
    assert len(set(mapd.PHASES)) == len(mapd.PHASES)
    assert set(TOP) | set(INNER) | {"prime"} == set(mapd.PHASES)


def _bids(cfg) -> tuple:
    """``bid`` where the fleet is wider than one bid pass."""
    return ("bid",) if cfg.num_agents > mapd._BID_LANES else ()


@pytest.mark.parametrize("conf,stale", [(WAREHOUSE, False), (EMPTY, False),
                                        (WAREHOUSE, True)],
                         ids=["warehouse", "empty", "warehouse-stale"])
def test_step_names_its_phases(conf, stale):
    # the stale path: radius 15, view refresh 2, commit delay 1 (ROADMAP A3)
    extra = STALE if stale else {}
    cfg = SolverConfig(**conf, **extra)
    assert cfg.stale_mode == stale
    text = _step_text(cfg, 3 * cfg.num_agents)
    _check_phases(text, TOP + ("sweep", "pack") + _bids(cfg)
                  + (tuple(STALE_PHASES) if stale else ()))
    _check_replan_body(text)
    if not stale:
        named = {c for p in _paths(text) for c in p}
        assert not named & set(STALE_PHASES)


@pytest.mark.parametrize("conf", [WAREHOUSE, EMPTY], ids=["warehouse", "empty"])
def test_solve_names_its_phases(conf):
    """The one-program solve runs the t=0 burst (``prime``) and the
    initial assignment ahead of its step loop; its done test is part of
    the step's bookkeeping (``record``)."""
    cfg = SolverConfig(**conf)
    text = _solve_text(cfg, cfg.num_agents)
    _check_phases(text, set(mapd.PHASES) - {"bid"} - set(STALE_PHASES)
                  | set(_bids(cfg)))
    _check_replan_body(text)
    assert any(p[1:4] == ["while", "cond", "record"] for p in _paths(text))


def test_bid_ops_run_once_per_pass():
    """The ops under ``bid`` sit in the bid-pass loop's own body, never in
    the task-chunk scan nested in it, so a trace counts their runs as
    passes (task chunks of one order: 1,320 chunks a pass)."""
    cfg = SolverConfig(**WAREHOUSE, assign_chunk=1)
    comps = _computations(_step_text(cfg, 3 * cfg.num_agents))

    def has_bid(lines):
        return any("bid" in p.split("/") for line in lines
                   for p in (_op_name(line) or "").split(";"))

    bodies = {c: re.search(r"body=%([\w.\-]+)", line).group(1)
              for c, lines in comps.items() for line in lines
              if " while(" in line}
    passes = [b for b in bodies.values() if has_bid(comps[b])]
    assert len(passes) == 1
    scans = [re.search(r"body=%([\w.\-]+)", line).group(1)
             for line in _loop_ops(comps, passes[0])
             if " while(" in line]
    assert scans
    for scan in scans:
        assert not has_bid(list(_loop_ops(comps, scan)))


def _frame_functions(text: str) -> dict:
    """{stack frame id: the function of the frame's own location} from
    the tables a compiled module's text opens with."""
    tables, section = {}, None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            break
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = tables.setdefault(line, {})
            continue
        m = re.match(r"(\d+) (.*)$", line)
        if m and section is not None:
            section[int(m.group(1))] = m.group(2)
    fn = {i: v.strip('"') for i, v in tables["FunctionNames"].items()}
    loc = {i: int(re.search(r"function_name_id=(\d+)", v).group(1))
           for i, v in tables["FileLocations"].items()}
    return {i: fn[loc[int(re.search(r"file_location_id=(\d+)", v).group(1))]]
            for i, v in tables["StackFrames"].items()}


@pytest.mark.parametrize("knobs", [STALE, STALE_NOW],
                         ids=["commit-next-step", "commit-at-once"])
def test_stale_phases_hold_their_functions(knobs):
    """Every op the compiled stale step keeps from ``_commit_pending``,
    ``_broadcast_view``, ``_view_occupancy`` and the two Rule-4 ring scans
    carries ``rules/commit``, ``rules/view`` or ``rules/walk`` (a fused
    op in at least one of the paths it joins)."""
    cfg = SolverConfig(**WAREHOUSE, **knobs)
    text = _step_text(cfg, 3 * cfg.num_agents)
    frames = _frame_functions(text)
    owner = {f: p for p, fs in STALE_PHASES.items() for f in fs}
    seen = dict.fromkeys(STALE_PHASES, 0)
    for line in text.splitlines():
        frame = re.search(r"stack_frame_id=(\d+)", line)
        op = _op_name(line)
        # a constant, or a broadcast of one, is shared by the compiler
        # between the ops that use it and keeps one user's metadata
        if (not frame or op is None
                or re.search(r" (constant|parameter)\(|broadcast\(%constant",
                             line)):
            continue
        phase = owner.get(frames[int(frame.group(1))])
        if phase is None:
            continue
        paths = [p.split("/") for p in op.split(";")]
        assert any(phase in p and "rules" in p[:p.index(phase)]
                   for p in paths), line
        seen[phase] += 1
    assert all(seen.values()), seen


def _strip(text: str) -> str:
    """A compiled module's text without its source tables and metadata:
    what the device runs."""
    lines = text.splitlines()
    body = [lines[0]] + [line for line in lines
                         if line.startswith(("%", "ENTRY", " ", "}"))]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(body))


@pytest.mark.parametrize("knobs", [{}, STALE], ids=["centralized", "stale"])
def test_scopes_are_metadata_only(knobs, monkeypatch):
    """The step compiled with its named scopes and with every scope made
    a no-op differs only in metadata."""
    cfg = SolverConfig(**WAREHOUSE, **knobs)
    jax.clear_caches()
    named = _strip(_step_text(cfg, 3 * cfg.num_agents))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    bare = _step_text(cfg, 3 * cfg.num_agents)
    jax.clear_caches()
    assert "rules" not in bare
    assert named == _strip(bare)
