"""Finding a cell's files by name.

- ``BENCHMARK.json`` (checkout root): the cell's entry, and which
  metrics it reports (a metric with a ``workloads`` key lists its cells;
  one without it is reported by every cell).
- ``workloads/<cell>.json``: traffic parameters, the driver ``kind``,
  the limits that decide ``correct``, the control.
- ``configs/<config>.json``: the deployment.
- ``harness/drivers/<kind>.py``: the driver, with ``run(run) -> dict``.
- ``metrics/<metric>.py``: a per-layer reader, with ``read(ctx)``
  returning a number, or None where it finds nothing to read.  Metrics
  that split one quantity by cell (``device_idle.tick``,
  ``device_idle.solve``) share the reader of their stem
  (``metrics/device_idle.py``) unless a file of their own exists.

A later PR adds a cell, configuration, driver or metric by adding files
and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell(bench: dict, name: str, root: Path = BENCH_DIR):
    """(BENCHMARK.json entry, workload file, configuration file)."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    work = load_json(root / "workloads" / f"{name}.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    conf = load_json(root.parent / conf_entry["file"])
    return entry, work, conf


def metrics_for(bench: dict, cell_name: str, section: str) -> list:
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


def _load(path: Path, modname: str):
    """The module at ``path``, loaded once per process."""
    mod = sys.modules.get(modname)
    if mod is not None and Path(mod.__file__) == path:
        return mod
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str, root: Path = BENCH_DIR):
    return _load(root / "harness" / "drivers" / f"{kind}.py",
                 f"bench_driver_{kind}")


def reader(metric: str, root: Path = BENCH_DIR):
    path = root / "metrics" / f"{metric}.py"
    if not path.exists():
        metric = metric.split(".")[0]
        path = root / "metrics" / f"{metric}.py"
    return _load(path, "bench_metric_" + metric.replace(".", "_"))
