#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell's files (``workloads/<cell>.json``, ``configs/<config>.json``)
and its driver (``harness/drivers/<kind>.py``) are found by name.  The
run builds its inputs from ``--seed``, warms up the cell's shapes
(``setup_s``), measures for ``--seconds``, then compares what the window
produced with the plain reference (``harness/reference.py``).

Last line of standard output: ``{"correct", "attempted", "failed",
"metrics", "device"[, "breakdown"], "checks"}``.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiled window.  Each number compared
with the reference is printed beside its limit on the last lines of
standard error and under ``checks``.  No TPU, or fewer chips than the
cell asks for: exit 3 and no result.

``--control`` runs the cell's control (the program with the path the
cell file names switched on); the benchmark's own runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent
# a fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = CHECKOUT / ".jax_cache"


@dataclass
class Run:
    name: str
    entry: dict          # BENCHMARK.json workload entry
    work: dict           # workloads/<cell>.json
    conf: dict           # configs/<config>.json
    seed: int
    seconds: float
    trace: bool
    control: bool
    t_start: float = T_START
    device: dict = field(default_factory=dict)

    def log(self, msg: str) -> None:
        print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def _env() -> None:
    """Before JAX is imported: the compile cache in the checkout, every
    program cached, the TPU runtime's logs kept off fixed paths."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(BENCH_DIR), str(CHECKOUT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def _check_lines(checks) -> list:
    out = []
    for name, value, op, limit in checks:
        ok = value <= limit if op == "<=" else value >= limit
        out.append((name, value, op, limit, ok))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    _env()
    from harness import device as devmod
    from harness import spec, trace as tracemod

    bench_path = CHECKOUT / "BENCHMARK.json"
    try:
        bench = spec.load_json(bench_path)
        entry, work, conf = spec.cell(bench, args.workload, BENCH_DIR)
    except (OSError, KeyError, StopIteration, ValueError) as e:
        print(f"benchmark: cannot load cell {args.workload!r}: {e}",
              file=sys.stderr, flush=True)
        return 2
    run = Run(args.workload, entry, work, conf, args.seed, args.seconds,
              bool(args.trace), args.control)
    run.device = devmod.require(int(entry["chips"]))
    driver = spec.driver(work["kind"], BENCH_DIR)
    res = driver.run(run)

    if run.trace:
        ctx = dict(res.get("layer", {}), run=run,
                   peaks=devmod.peaks(run.device["kind"]))
        metrics = {}
        for m in spec.metrics_for(bench, run.name, "per_layer"):
            v = spec.reader(m["name"], BENCH_DIR).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": res["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in spec.metrics_for(bench, run.name, "end_to_end")}

    checks = _check_lines(res["checks"])
    correct = all(c[4] for c in checks)
    dev = dict(run.device, memory_peak_bytes=int(res["memory_peak_bytes"]))
    line = {"correct": correct, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics, "device": dev}
    if run.trace:
        ev = res["layer"]["trace"]
        dev["busy_s"] = tracemod.busy_s(ev)
        dev["window_s"] = res["layer"]["window_s"]
        line["breakdown"] = {"device_ops": tracemod.top_ops(ev),
                             "idle_gaps": tracemod.idle_gaps(ev)}
    line["checks"] = {c[0]: {"value": c[1], "limit": f"{c[2]} {c[3]}"}
                      for c in checks}
    for name, value, op, limit, ok in checks:
        print(f"check {name} {value} {op} {limit} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
