"""CPU tests of the benchmark (not the driver's tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
for p in (str(BENCH), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIGS = {
    "tiny-warehouse": {
        "source": "test", "map": {"kind": "warehouse", "height": 28,
                                  "width": 100, "shelf_h": 2,
                                  "shelf_w": 20, "aisle": 2, "margin": 1},
        "agents": 440, "orders": 1320,
        "solver": {"replan_chunk": 16, "replan_chunk_small": 4,
                   "max_timesteps": 400, "record_paths": False},
        "assumed": [], "reduced": []},
    "tiny-empty": {
        "source": "test", "map": {"kind": "empty", "height": 20,
                                  "width": 20},
        "agents": 8, "orders": 8,
        "solver": {"replan_chunk": 8, "max_timesteps": 400,
                   "record_paths": True},
        "assumed": [], "reduced": []},
}
TINY_CELLS = {
    "tiny.ticks": {"kind": "offline_ticks", "config": "tiny-warehouse",
                   "traffic": "ticks", "instance_seed": 0,
                   "segment": {"start": 3, "ticks": 4},
                   "limits": {"illegal_moves": 0, "replay_diff": 0,
                              "plan_mismatch": 0},
                   "control": {"max_sweep_rounds": 1}},
    "tiny.batch": {"kind": "offline_solves", "config": "tiny-empty",
                   "traffic": "batch", "instance_seed": 0, "cycle": 3,
                   "limits": {"illegal_moves": 0, "repeat_diff": 0,
                              "plan_mismatch": 0, "batches_min": 3},
                   "control": {"swap_rounds": 0}},
}


def make_tree(tmp: Path) -> Path:
    """A checkout holding the benchmark and the program, plus tiny cells
    made only of new files and their BENCHMARK.json entries."""
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp / "p2p_distributed_tswap_tpu").symlink_to(
        CHECKOUT / "p2p_distributed_tswap_tpu")
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for name, conf in TINY_CONFIGS.items():
        f = f"benchmark/configs/{name}.json"
        (tmp / f).write_text(json.dumps(dict(conf, name=name)))
        bench["configs"].append({"name": name, "source": "test", "file": f,
                                 "reduced": [], "why": "test"})
    for name, work in TINY_CELLS.items():
        (tmp / "benchmark" / "workloads" / f"{name}.json").write_text(
            json.dumps(work))
        bench["workloads"].append({"name": name, "config": work["config"],
                                   "traffic": work["traffic"], "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        for real, tiny in (("lorr10k.lifelong", "tiny.ticks"),
                           ("ref50.batch", "tiny.batch")):
            if real in m.get("workloads", []):
                m["workloads"].append(tiny)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def load_run(tree: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(tree)))}", tree / "benchmark" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The tiny tree with the chip check stubbed: the run goes on on the
    CPU as if it held one v5e."""
    from harness import device

    monkeypatch.setattr(device, "require", lambda chips: dict(FAKE_DEVICE))
    return make_tree(tmp_path)


def run_cell(tree: Path, capsys, *args) -> dict:
    rc = load_run(tree).main(list(args))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
