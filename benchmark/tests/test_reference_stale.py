"""The decentralized cell end to end on the CPU at a tiny size: a sound
run is ``correct``, a traced run reports the fleet's counters, and the
stale reference agrees with the program where the program is sound (the
step-for-step comparison over many cases is tier 1's
``tests/test_stale_reference.py``)."""

import json

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT
from harness import reference, reference_stale, world
from stale_cell import add_cell, driver, run_decent


@pytest.fixture
def decent(tree):
    return add_cell(tree)


def test_sound_run_is_correct(decent, capsys):
    line = run_decent(decent, capsys, seed=2**33 + 5)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tick_ms", "tick_p95_ms", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["checks"].values())


def test_traced_run_reports_the_fleet(decent, capsys):
    """On the CPU the trace has no TPU plane: the device readers stay
    silent; the fleet's counters report: robots moved, goals exchanged."""
    line = run_decent(decent, capsys, trace=1)
    assert line["correct"], line["checks"]
    assert line["metrics"]["moved.decent"]["value"] > 0
    assert line["metrics"]["exchanges.decent"]["value"] >= 0
    assert set(line["metrics"]) <= {"moved.decent", "exchanges.decent",
                                    "device_idle.decent"}


def test_counters_are_the_tick_means(decent, capsys, monkeypatch):
    """``moved`` counts the robots whose fetched position changed and
    ``exchanges`` the reference's commits, tick by tick of the first
    replay; the readers average them."""
    drv = driver(decent)
    seen = {}
    real = drv.run

    def run(run, program=drv.Program):
        res = real(run, program)
        seen.update(res["layer"]["fleet"])
        return res

    monkeypatch.setattr(drv, "run", run)
    line = run_decent(decent, capsys, trace=1)
    ticks = json.loads((decent / "benchmark" / "workloads" /
                        "tiny.decent.json").read_text())["segment"]["ticks"]
    assert len(seen["moved"]) == len(seen["exchanges"]) == ticks
    assert line["metrics"]["moved.decent"]["value"] == \
        pytest.approx(np.mean(seen["moved"]))
    assert line["metrics"]["exchanges.decent"]["value"] == \
        pytest.approx(np.mean(seen["exchanges"]))


def test_offline_ticks_cells_keep_their_judge(decent, capsys):
    """Loading ``offline_ticks_stale`` leaves ``offline_ticks`` judged by the
    centralized reference."""
    from conftest import run_cell

    run_decent(decent, capsys)
    line = run_cell(decent, capsys, "--workload", "tiny.ticks", "--seed",
                    "5", "--seconds", "2", "--trace", "0")
    assert line["correct"], line["checks"]


def test_exchanges_count_commits():
    """A swap pair counts two, a push one, a ring member one: the
    count is the agents whose goal a commit changes."""
    free = world.build_map({"kind": "empty", "height": 6, "width": 6})
    sim = reference_stale.StaleSim(free, [0, 1, 2, 3], [[4, 5]],
                                   radius=15, refresh=2, delay=1)
    sim.pend_from = np.array([1, 0, 2, 3])
    sim.pend_push = np.array([-1, -1, -1, 9])
    sim.step()
    assert sim.exchanges[-1] == 3


def test_decent_config_is_lorr_with_the_stale_knobs():
    """The decentralized configuration is the LoRR one, but for the
    stale knobs, and its reference options are the knobs."""
    conf = json.loads((BENCH / "configs" /
                       "lorr-warehouse-10k-decent.json").read_text())
    lorr = json.loads((BENCH / "configs" /
                       "lorr-warehouse-10k.json").read_text())
    for key in ("map", "agents", "orders"):
        assert conf[key] == lorr[key]
    knobs = {k: v for k, v in conf["solver"].items()
             if k not in lorr["solver"]}
    assert knobs == {"visibility_radius": 15, "view_refresh_steps": 2,
                     "swap_commit_delay": 1}
    assert {k: conf["solver"][k] for k in lorr["solver"]} == lorr["solver"]
    opts = reference_stale.sim_options(conf["solver"])
    assert (opts["radius"], opts["refresh"], opts["delay"]) == (15, 2, 1)
    with pytest.raises(ValueError):
        reference.sim_options(conf["solver"])
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    names = {w["name"]: w for w in bench["workloads"]}
    assert names["lorr10k-decent.lifelong"]["config"] == conf["name"]


def test_program_hlo_is_the_windows_step():
    """The decentralized cell's step compiled again from its files is the
    module its window ran, the observation outputs included (tiny cell),
    so the trace's ops find their phases."""
    from types import SimpleNamespace

    from harness import scopes, spec
    from harness.solver import solver_config
    from stale_cell import CELL, CONFIG

    run = SimpleNamespace(work=CELL, conf=CONFIG, control=False)
    free = world.build_map(CONFIG["map"])
    first = world.fleet(free, int(CONFIG["agents"]), int(CONFIG["orders"]),
                        int(CELL["instance_seed"]))
    prog = spec.driver("offline_ticks_stale", BENCH).Program(
        solver_config(run), free, *first)
    prog.compile(prog.prepare())
    text = prog._step.as_text()
    assert scopes.program_hlo(run) == text
    names = scopes.op_names(text).values()
    for phase in ("commit", "view", "walk"):
        assert any(scopes.under(op, "rules", phase) for op in names), phase
