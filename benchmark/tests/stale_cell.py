"""A tiny decentralized cell, ``tiny.decent``, added to the tiny tree of
``conftest.make_tree`` by new files and entries only: the tiny shelf
floor with the decentralized cell's stale knobs, run by
``harness/drivers/offline_ticks_stale.py``."""

import json

from conftest import TINY_CONFIGS, TINY_CELLS, run_cell

STALE_KNOBS = {"visibility_radius": 15, "view_refresh_steps": 2,
               "swap_commit_delay": 1}
CONFIG = dict(TINY_CONFIGS["tiny-warehouse"],
              solver=dict(TINY_CONFIGS["tiny-warehouse"]["solver"],
                          **STALE_KNOBS))
CELL = dict(TINY_CELLS["tiny.ticks"], kind="offline_ticks_stale",
            config="tiny-warehouse-decent", traffic="decent",
            control={"swap_commit_delay": 0})


def add_cell(tree):
    """``tree`` with ``tiny.decent``: its files, its BENCHMARK.json
    entries, and its name beside ``lorr10k-decent.lifelong`` in every
    metric's list."""
    f = "benchmark/configs/tiny-warehouse-decent.json"
    (tree / f).write_text(json.dumps(dict(CONFIG,
                                          name="tiny-warehouse-decent")))
    (tree / "benchmark" / "workloads" / "tiny.decent.json").write_text(
        json.dumps(CELL))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-warehouse-decent",
                             "source": "test", "file": f, "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny.decent",
                               "config": "tiny-warehouse-decent",
                               "traffic": "decent", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lorr10k-decent.lifelong" in m.get("workloads", []):
            m["workloads"].append("tiny.decent")
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    return tree


def run_decent(tree, capsys, *extra, trace=0, seed=5):
    return run_cell(tree, capsys, "--workload", "tiny.decent", "--seed",
                    str(seed), "--seconds", "2", "--trace", str(trace),
                    *extra)


def driver(tree):
    from harness import spec
    return spec.driver("offline_ticks_stale", tree / "benchmark")
