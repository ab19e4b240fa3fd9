"""The reader of ``assign_bids.tick``: bid passes per traced tick, read
from the ops under ``assign/bid`` on a synthetic scoped event list, and
silent where the program names no ``bid`` scope."""

from types import SimpleNamespace

from conftest import BENCH
from harness import scopes, spec


def _ev(name, start, scope):
    return [f"%{name} = s32[8]{{0}} fusion(s32[8]{{0}} %x)", start, 1, scope]


BODY = "jit(mapd_step)/assign/cond/branch_1_fun/while/body/while/body"


def _tick(passes, chunks, start=0):
    """One tick's assignment: ``passes`` bid passes, each gathering its
    lanes under ``bid``, scanning ``chunks`` task chunks outside it, then
    scattering its bids under ``bid``."""
    out, t = [], start
    for _ in range(passes):
        out.append(_ev("fusion.1", t, f"{BODY}/bid/gather"))
        for _ in range(chunks):
            out.append(_ev("fusion.2", t + 1, f"{BODY}/while/body/reduce"))
            t += 1
        out.append(_ev("fusion.3", t + 1, f"{BODY}/bid/scatter"))
        t += 2
    out.append(_ev("fusion.4", t, "jit(mapd_step)/replan/while/body/pack/or"))
    return out, t + 1


def _ctx(device, ticks):
    ev = {"device": {"/device:TPU:0": device}, "host": []}
    return {"trace": ev, "scoped_trace": ev,
            "run": SimpleNamespace(work={"segment": {"start": 0,
                                                     "ticks": ticks}})}


def _read(ctx):
    return spec.reader("assign_bids.tick", BENCH).read(ctx)


def test_reads_passes_not_task_chunks():
    """Two ticks of 1 and 2 passes, 5 task chunks a pass: 1.5 passes a
    tick, where the chunk scan's ops ran 15 times."""
    first, t = _tick(1, 5)
    second, _ = _tick(2, 5, t)
    ctx = _ctx(first + second, 2)
    assert scopes.runs(ctx["trace"], "assign") == 15
    assert _read(ctx) == 1.5


def test_silent_without_the_scope(monkeypatch):
    """A program whose assignment bids all rows at once (no ``bid`` op),
    no trace, and a program that names no phases: no reading."""
    plain = [_ev("fusion.2", 0, f"{BODY}/while/body/reduce"),
             _ev("fusion.4", 1, "jit(mapd_step)/replan/while/body/pack/or")]
    assert _read(_ctx(plain, 1)) is None
    ctx = _ctx([], 1)
    ctx["trace"] = ctx["scoped_trace"] = None
    assert _read(ctx) is None

    def compile_nothing(run):
        raise AssertionError("compiled without phases")

    monkeypatch.setattr(scopes, "PHASES", ())
    monkeypatch.setattr(scopes, "program_hlo", compile_nothing)
    passes, _ = _tick(2, 3)
    ctx = _ctx(passes, 1)
    del ctx["scoped_trace"]
    assert _read(ctx) is None
