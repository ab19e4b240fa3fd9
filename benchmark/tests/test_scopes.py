"""Device time per named phase: the op_name map, the attribution helper
(``harness/scopes.py``) and the six phase readers, on a synthetic event
list with known answers and on one tick recorded on the chip; the cell's
program compiled again to the module its driver ran."""

import json
from types import SimpleNamespace

import pytest

from conftest import BENCH
from harness import scopes, spec, trace

READERS = ("assign_ms.tick", "replan_ms.tick", "rules_ms.tick",
           "move_ms.tick", "sweep_ms.tick", "replan_chunks.tick")
SOLVE_READERS = ("assign_ms.solve", "replan_ms.solve", "rules_ms.solve",
                 "move_ms.solve")


def _ev(name, op, start, dur, scope):
    return [f"%{name} = s32[8]{{0}} {op}(s32[8]{{0}} %x)", start, dur, scope]


# one tick: assignment, a replan loop of two chunks (three sweep op runs,
# two packs, two scatters), rules, a fused move op that merged two
# op_names, a compiler copy under no phase, and a sweep under ``prime``
SYN = {"device": {"/device:TPU:0": [
    _ev("fusion.1", "fusion", 0, 100, "jit(f)/assign/cond/gather"),
    ["%while.9 = (s32[8]{0}) while((s32[8]{0}) %t), body=%b", 100, 60,
     "jit(f)/replan/while"],
    _ev("fusion.2", "fusion", 100, 10, "jit(f)/replan/while/body/sweep/min"),
    _ev("fusion.2", "fusion", 110, 10, "jit(f)/replan/while/body/sweep/min"),
    _ev("fusion.3", "fusion", 120, 5, "jit(f)/replan/while/body/pack/or"),
    _ev("fusion.4", "fusion", 125, 1, "jit(f)/replan/while/body/scatter"),
    _ev("fusion.2", "fusion", 130, 10, "jit(f)/replan/while/body/sweep/min"),
    _ev("fusion.3", "fusion", 140, 5, "jit(f)/replan/while/body/pack/or"),
    _ev("fusion.4", "fusion", 145, 1, "jit(f)/replan/while/body/scatter"),
    _ev("fusion.5", "fusion", 160, 40, "jit(f)/rules/while/body/gather"),
    _ev("fusion.6", "fusion", 200, 30,
        "jit(f)/move/while/body/gather;jit(f)/move/while/body/scatter"),
    _ev("copy.7", "copy", 230, 7, ""),
    _ev("fusion.8", "fusion", 237, 9, "jit(f)/prime/while/body/sweep/min"),
]}, "host": []}


def _ctx(ev, **work):
    """A reader's context whose trace already carries its scopes."""
    return {"trace": ev, "scoped_trace": ev,
            "run": SimpleNamespace(work=work)}


def test_under_matches_whole_components_in_order():
    s = "jit(f)/replan/while/body/sweep/min"
    assert scopes.under(s, "replan") and scopes.under(s, "replan", "sweep")
    assert not scopes.under(s, "sweep", "replan")
    assert not scopes.under("jit(f)/replanner/x", "replan")
    assert scopes.under("jit(f)/a/x;jit(f)/move/y", "move")
    assert not scopes.under("", "move")


def test_split_on_synthetic():
    """A phase's time is the union of its ops' intervals: the replan
    loop's 60 ns hold 42 ns of ops and 18 of loop control; the copy under
    no phase is the 7 ns left of the busy 246."""
    split = scopes.split_s(SYN)
    ns = {p: round(v * 1e9) for p, v in split.items()}
    assert ns == {"assign": 100, "replan": 60, "sweep": 39, "pack": 10,
                  "rules": 40, "move": 30, "record": 0, "prime": 9,
                  None: 7}
    assert scopes.phase_s(SYN, "replan", "sweep") == pytest.approx(30e-9)
    # a loop is not a leaf op: pack ran once per chunk
    assert scopes.runs(SYN, "replan", "pack") == 2
    assert scopes.runs(SYN, "replan", "sweep") == 3


def test_readers_on_synthetic():
    ctx = _ctx(SYN, segment={"start": 0, "ticks": 2})
    got = {m: spec.reader(m, BENCH).read(ctx) for m in READERS}
    assert got == pytest.approx({
        "assign_ms.tick": 100e-6 / 2, "replan_ms.tick": 60e-6 / 2,
        "rules_ms.tick": 40e-6 / 2, "move_ms.tick": 30e-6 / 2,
        "sweep_ms.tick": 30e-6 / 2, "replan_chunks.tick": 1.0})
    ctx = _ctx(SYN, cycle=4)
    got = {m: spec.reader(m, BENCH).read(ctx) for m in SOLVE_READERS}
    assert got == pytest.approx({
        "assign_ms.solve": 100e-6 / 4, "replan_ms.solve": 60e-6 / 4,
        "rules_ms.solve": 40e-6 / 4, "move_ms.solve": 30e-6 / 4})


def test_readers_silent_without_scopes(monkeypatch):
    """No trace, a trace whose ops the program's HLO does not name, a
    program whose op_names name no phase, and a program without
    ``PHASES`` (nothing is compiled): every reader is silent."""
    small = json.loads((BENCH / "tests" / "data" / "trace_small.json")
                       .read_text())
    bare = {"device": {"/device:TPU:0": [
        _ev("fusion.1", "fusion", 0, 10, "jit(mapd_step)/while/body/gather")]},
        "host": []}
    for ev in (None, scopes.attach(small, {}), bare):
        ctx = _ctx(ev, segment={"start": 0, "ticks": 1}, cycle=1)
        for m in READERS + SOLVE_READERS:
            assert spec.reader(m, BENCH).read(ctx) is None, (m, ev)

    def compile_nothing(run):
        raise AssertionError("compiled without phases")

    monkeypatch.setattr(scopes, "PHASES", ())
    monkeypatch.setattr(scopes, "program_hlo", compile_nothing)
    ctx = {"trace": small, "run": SimpleNamespace(work={"cycle": 1})}
    for m in READERS + SOLVE_READERS:
        assert spec.reader(m, BENCH).read(ctx) is None, m


HLO = """HloModule jit_f

%body.1 (p: (s32[8])) -> (s32[8]) {
  %p = (s32[8]{0}) parameter(0)
  %copy.3 = s32[8]{0} copy(s32[8]{0} %g)
  ROOT %fusion.2 = s32[8]{0} fusion(s32[8]{0} %copy.3), kind=kLoop, calls=%fused.4, metadata={op_name="jit(f)/replan/while/body/sweep/min" stack_frame_id=3}
}

%fused.4 (q: s32[8]) -> s32[8] {
  %q = s32[8]{0} parameter(0)
  ROOT %min.5 = s32[8]{0} minimum(s32[8]{0} %q, s32[8]{0} %q), metadata={op_name="jit(f)/replan/while/body/sweep/min"}
}

ENTRY %main.6 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %copy-start.7 = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(s32[8]{0} %x)
  ROOT %while.8 = (s32[8]{0}) while((s32[8]{0}) %t), condition=%cond.9, body=%body.1, metadata={op_name="jit(f)/replan/while"}
}
"""


def test_op_names_inherit_the_running_instruction():
    names = scopes.op_names(HLO)
    assert names["fusion.2"] == "jit(f)/replan/while/body/sweep/min"
    assert names["while.8"] == "jit(f)/replan/while"
    # a compiler copy in the loop body runs as part of the replan loop
    assert names["copy.3"] == "jit(f)/replan/while"
    assert names["copy-start.7"] == "" and names["x"] == ""


def test_op_names_of_the_compiled_step():
    """Every op that runs in the compiled step's replan loop, metadata or
    not, falls under ``replan`` (CPU compile of a small warehouse)."""
    import jax
    import jax.numpy as jnp
    from p2p_distributed_tswap_tpu.core.config import SolverConfig
    from p2p_distributed_tswap_tpu.solver import mapd

    cfg = SolverConfig(height=28, width=100, num_agents=64,
                       replan_chunk_small=4, record_paths=False)
    args = (jax.ShapeDtypeStruct((64,), jnp.int32),
            jax.ShapeDtypeStruct((192, 2), jnp.int32),
            jax.ShapeDtypeStruct((28, 100), jnp.bool_))
    state, tasks = jax.eval_shape(lambda *a: mapd.prepare_state(cfg, *a),
                                  *args)
    text = jax.jit(mapd.mapd_step, static_argnums=0).lower(
        cfg, state, tasks, args[2]).compile().as_text()
    names = scopes.op_names(text)
    loop = [n for n, op in names.items() if op.endswith("replan/while")
            and n.startswith("while")]
    assert len(loop) == 1
    body = next(line for line in text.splitlines()
                if f"%{loop[0]} = " in line).split("body=%")[1].split(",")[0]
    inside = [line.split(" = ")[0].split("%")[-1]
              for line in text.split(f"%{body} ")[1].split("\n}")[0]
              .splitlines()[1:] if " = " in line]
    assert inside
    assert all(scopes.under(names[n], "replan") for n in inside)
    for phase in ("assign", "replan", "sweep", "pack", "rules", "move"):
        assert any(scopes.under(op, phase) for op in names.values()), phase


def test_attach_scopes_from_the_hlo():
    """An op event takes its instruction's op_name; an op the HLO does not
    name takes "", and the host annotations pass through."""
    ev = {"device": {"/device:TPU:0": [
        ["%fusion.2 = s32[8]{0} fusion(s32[8]{0} %copy.3)", 5, 3],
        ["%copy.3 = s32[8]{0} copy(s32[8]{0} %g)", 1, 2],
        ["%fusion.99 = s32[8]{0} fusion(s32[8]{0} %x)", 9, 1],
        ["not an instruction", 10, 1]]},
        "host": [["bench.fetch", 0, 20]]}
    got = scopes.attach(ev, scopes.op_names(HLO))
    assert [e[3] for e in got["device"]["/device:TPU:0"]] == [
        "jit(f)/replan/while/body/sweep/min", "jit(f)/replan/while", "", ""]
    assert [e[:3] for e in got["device"]["/device:TPU:0"]] == \
        ev["device"]["/device:TPU:0"]
    assert got["host"] == ev["host"]


@pytest.mark.parametrize("cell", ["tiny.ticks", "tiny.batch"])
def test_program_hlo_is_the_drivers_module(cell, monkeypatch):
    """The program compiled again from the cell's files is the module the
    driver compiled and ran, instruction for instruction (tiny cells)."""
    from conftest import TINY_CELLS, TINY_CONFIGS
    from harness import world
    from harness.solver import solver_config

    work = TINY_CELLS[cell]
    run = SimpleNamespace(work=work, conf=TINY_CONFIGS[work["config"]],
                          control=False)
    free = world.build_map(run.conf["map"])
    first = world.fleet(free, int(run.conf["agents"]),
                        int(run.conf["orders"]), int(work["instance_seed"]))
    drv = spec.driver(work["kind"], BENCH)
    if work["kind"] == "offline_solves":
        ran = drv.Program(solver_config(run), free, [first])._solve
    else:
        prog = drv.Program(solver_config(run), free, *first)
        prog.compile(prog.prepare())
        ran = prog._step
    text = ran.as_text()
    assert scopes.program_hlo(run) == text
    names = scopes.op_names(text)
    assert any(scopes.under(op, "assign") for op in names.values())
    # the readers compile once per run
    calls = []
    monkeypatch.setattr(scopes, "program_hlo",
                        lambda r: calls.append(r) or text)
    ev = {"device": {"/device:TPU:0": [[f"%{n} = s32[1]{{0}} add()", 0, 1]
                                       for n in names]}, "host": []}
    ctx = {"trace": ev, "run": run}
    for m in READERS if "segment" in work else SOLVE_READERS:
        spec.reader(m, BENCH).read(ctx)
    assert len(calls) == 1 and ctx["scoped_trace"] is not None


def test_recorded_scoped_tick():
    """One lorr10k.lifelong tick (tick 51, the segment's first) recorded
    on a v5e: the op events of at least 0.5 us (1,756 of 6,089, 99.4% of
    the op time), each name cut to instruction, shape and opcode, with the
    scope taken from the compiled step's HLO.  The host replay of that
    tick flags 31 fields: 8 chunks of 4."""
    ev = json.loads((BENCH / "tests" / "data" / "trace_scoped.json")
                    .read_text())
    busy = trace.busy_s(ev)
    assert busy == pytest.approx(0.029911333)
    split = scopes.split_s(ev)
    assert split["assign"] == pytest.approx(0.010490897)
    assert split["replan"] == pytest.approx(0.004532228)
    assert split["rules"] == pytest.approx(0.006951508)
    assert split["move"] == pytest.approx(0.0079367)
    assert scopes.phase_s(ev, "replan", "sweep") <= split["replan"]
    assert scopes.phase_s(ev, "replan", "pack") <= split["replan"]
    named = sum(split[p] for p in ("assign", "replan", "rules", "move",
                                   "record", "prime"))
    # the top phases partition the busy time
    assert named + split[None] == pytest.approx(busy)
    assert named >= 0.95 * busy and split[None] < 0.001 * busy
    assert scopes.runs(ev, "replan", "pack") == 8
    ctx = _ctx(ev, segment={"start": 50, "ticks": 1})
    got = {m: spec.reader(m, BENCH).read(ctx) for m in READERS}
    assert got["replan_chunks.tick"] == 8
    assert got["assign_ms.tick"] == pytest.approx(10.490897)
    assert got["sweep_ms.tick"] == pytest.approx(
        1e3 * scopes.phase_s(ev, "replan", "sweep"))
    # the largest ops the ledger could not name
    names = {n: s for n, s in trace.top_ops(ev, 3)}
    assert list(names)[:2] == ["fusion.353 fusion", "fusion.340 fusion"]
    scope = {trace.hlo_parts(e[0])[0]: e[3] for e in ev["device"]
             ["/device:TPU:0"]}
    assert scopes.under(scope["fusion.353"], "assign")
    assert scopes.under(scope["fusion.340"], "rules")


def test_small_trace_reductions_unchanged():
    """The trace recorded before scopes reduces as it always did."""
    ev = json.loads((BENCH / "tests" / "data" / "trace_small.json")
                    .read_text())
    assert trace.busy_s(ev) == 0.112303946
    assert trace.top_ops(ev) == [
        ["fusion.260 fusion", 0.006400774],
        ["reshape.783 reshape", 0.000409655],
        ["copy.52 copy", 0.000363757], ["copy.50 copy", 0.000362511],
        ["copy.49 copy", 0.000362408], ["copy.51 copy", 0.000362373],
        ["copy.56 copy", 0.000357639], ["copy.53 copy", 0.000357599],
        ["copy.55 copy", 0.000357433], ["copy.54 copy", 0.000357383]]
    assert trace.idle_gaps(ev) == [["bench.tick", 7.36e-07]]
