"""A cell defined only by new files loads and runs; the real cells'
files load; every driver's result line has the contract's keys."""

import json

import pytest

from conftest import BENCH, CHECKOUT, run_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("cell,e2e", [
    ("tiny.ticks", {"tick_ms", "tick_p95_ms", "setup_s"}),
    ("tiny.batch", {"solve_ms", "setup_s"}),
])
def test_new_cell_runs_correct(tree, capsys, cell, e2e):
    line = run_cell(tree, capsys, "--workload", cell, "--seed",
                    str(2**31 + 7), "--seconds", "2", "--trace", "0")
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == e2e
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("cell,layer", [
    ("tiny.ticks", {"device_idle.tick", "prime_s"}),
    ("tiny.batch", {"device_idle.solve"}),
])
def test_traced_run_reports_layers(tree, capsys, cell, layer):
    """On the CPU the trace has no TPU plane: the device readers find
    nothing and stay silent; host readers still report."""
    line = run_cell(tree, capsys, "--workload", cell, "--seed", "3",
                    "--seconds", "2", "--trace", "1")
    assert line["correct"], line["checks"]
    assert "breakdown" in line
    assert set(line["metrics"]) <= layer
    if cell == "tiny.ticks":
        assert line["metrics"]["prime_s"]["value"] > 0


def test_real_cells_load():
    from harness import spec

    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        entry, work, conf = spec.cell(bench, w["name"], BENCH)
        assert work["config"] == entry["config"] == conf["name"]
        assert spec.driver(work["kind"], BENCH).run
        for m in spec.metrics_for(bench, w["name"], "per_layer"):
            assert spec.reader(m["name"], BENCH).read


@pytest.mark.parametrize("cell", ["lorr10k.lifelong", "ref50.batch"])
def test_no_tpu_exits_nonzero_without_result(cell):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=CHECKOUT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 3 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
