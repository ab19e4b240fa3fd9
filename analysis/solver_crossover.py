"""Live-fleet native-vs-tpu planning crossover (ISSUE 3 acceptance).

analysis/crossover_sweep.py measured the two planners in ISOLATION
(tswap_bench vs a synthetic request driver); this harness measures them in
a LIVE fleet: busd + the real centralized manager + (for tpu) the real
solverd, with N simulated agents closing the control loop over the bus —
they adopt tasks, follow move_instructions, publish position updates and
dones, so the manager plans a genuinely churning fleet every 500 ms tick.

Per agent-count rung the harness runs up to three variants:

- ``native``  --solver=cpu: the manager's sequential TSWAP + BFS cache.
  End-to-end ms/tick = the manager's own ``tick_ms`` histogram (plan +
  emit + adopt, from its live-metrics beacon).
- ``packed``  --solver=tpu on the packed delta wire (the fast path).
  End-to-end ms/tick = ``manager.plan_rtt_ms`` (request publish -> fresh
  response applied) — everything the fleet pays beyond the native path.
- ``json``    --solver=tpu on the legacy JSON wire, for the wire-bytes
  comparison (``bus.bytes_*{topic="solver"}`` registry counters).

All numbers come from the processes' own ``mapd.metrics`` beacons
(registry snapshots), diffed across the measurement window — no
instrumentation is added for the benchmark.  Evidence for the fast-path
mechanics rides along: ``solverd.delta_agents`` per tick (O(churn)
upload), ``solverd.decode_bytes``, ``solverd.pipeline_overlap_ms``.

Usage:
  python analysis/solver_crossover.py --out results/solver_crossover.json
  python analysis/solver_crossover.py --counts 50,300 --window 10  # smoke

The committed artifact runs solverd with --cpu (JAX CPU backend): its
numbers are CPU numbers, not device timings.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from p2p_distributed_tswap_tpu.obs.registry import hist_quantile  # noqa: E402
from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient  # noqa: E402
from p2p_distributed_tswap_tpu.runtime.fleet import (  # noqa: E402
    BUILD_DIR, ensure_built, wait_for_log)
# The sim-agent loop that used to live here (SimFleet) was generalized
# into the reusable, shard-aware, pos1-speaking pool behind the fleetsim
# load harness (ISSUE 7); this harness now drives the same pool.
from p2p_distributed_tswap_tpu.runtime.simagent import SimAgentPool  # noqa: E402,E501

TICK_MS = 500.0


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class BeaconWatch:
    """Collect mapd.metrics beacons per process name."""

    def __init__(self, port: int):
        self.bus = BusClient(port=port, peer_id="beaconwatch")
        self.bus.subscribe("mapd.metrics")
        self.samples = {}  # proc -> list of (mono_t, metrics)

    def pump(self, budget_s: float):
        end = time.monotonic() + budget_s
        while True:
            now = time.monotonic()
            if now >= end:
                return
            f = self.bus.recv(timeout=min(0.2, end - now))
            if not f or f.get("op") != "msg":
                continue
            d = f.get("data") or {}
            if d.get("type") == "metrics_beacon":
                self.samples.setdefault(d.get("proc"), []).append(
                    (time.monotonic(), d.get("metrics") or {}))

    def window(self, proc: str):
        """(first, last) snapshots of a proc, or None."""
        s = self.samples.get(proc) or []
        if len(s) < 2:
            return None
        return s[0][1], s[-1][1]

    def close(self):
        self.bus.close()


def _counter(m, name, topic=None):
    total = 0.0
    for key, v in (m.get("counters") or {}).items():
        if key == name or (key.startswith(name + "{")
                           and (topic is None or f'topic="{topic}"' in key)):
            if topic is None or "topic=" not in key \
                    or f'topic="{topic}"' in key:
                total += v
    return total


def _hist_delta(first, last, name):
    h0 = (first.get("hists") or {}).get(name)
    h1 = (last.get("hists") or {}).get(name)
    if h1 is None:
        return None
    if h0 is None:
        h0 = {"buckets": h1["buckets"], "counts": [0] * len(h1["counts"]),
              "sum": 0.0, "count": 0}
    counts = [b - a for a, b in zip(h0["counts"], h1["counts"])]
    return {"buckets": h1["buckets"], "counts": counts,
            "sum": h1["sum"] - h0["sum"], "count": h1["count"] - h0["count"]}


def run_variant(variant: str, n: int, side: int, map_file: str,
                window_s: float, settle_s: float, cpu: bool) -> dict:
    port = _free_port()
    procs = []
    logs = []

    def spawn(name, cmd, stdin=None, env=None):
        import os

        log = open(f"/tmp/crossover_{name}_{variant}_{n}.log", "w")
        logs.append(log)
        p = subprocess.Popen(cmd, stdin=stdin, stdout=log,
                             stderr=subprocess.STDOUT,
                             env=dict(os.environ, **(env or {})))
        procs.append(p)
        return p

    sim = watch = None
    try:
        spawn("bus", [str(BUILD_DIR / "mapd_bus"), str(port)])
        time.sleep(0.3)
        if variant != "native":
            sd_cmd = [sys.executable, "-m",
                      "p2p_distributed_tswap_tpu.runtime.solverd",
                      "--port", str(port), "--map", map_file,
                      "--warm", str(n)]
            if cpu:
                sd_cmd.append("--cpu")
            sd_proc = spawn("solverd", sd_cmd)
            if not wait_for_log(
                    f"/tmp/crossover_solverd_{variant}_{n}.log",
                    "solverd up", 900, proc=sd_proc):
                raise RuntimeError("solverd never became ready")
        mgr_env = {"JG_PLAN_CODEC": "packed" if variant == "packed"
                   else "json"}
        mgr = spawn("manager",
                    [str(BUILD_DIR / "mapd_manager_centralized"),
                     "--port", str(port), "--map", map_file,
                     "--solver", "cpu" if variant == "native" else "tpu",
                     "--max-tracked-agents", str(n + 16)],
                    stdin=subprocess.PIPE, env=mgr_env)
        time.sleep(0.5)
        sim = SimAgentPool(n, side, port=port)
        watch = BeaconWatch(port)
        sim.heartbeat_all()
        sim.pump(2.0)
        mgr.stdin.write(f"tasks {n}\n".encode())
        mgr.stdin.flush()
        # settle: tasks dispatch, caches warm, failover window closes
        t_end = time.monotonic() + settle_s
        while time.monotonic() < t_end:
            sim.pump(0.5)
            watch.pump(0.05)
        if variant == "packed":
            # deferred-field drain: the initial task burst queues N fresh
            # goal sweeps that run in solverd's idle windows — the
            # steady-state measurement starts once the queue is empty
            # (solverd.field_queue gauge rides its beacon)
            drain_end = time.monotonic() + 600
            while time.monotonic() < drain_end:
                sim.pump(0.5)
                watch.pump(0.1)
                s = watch.samples.get("solverd") or []
                if s:
                    q = (s[-1][1].get("gauges") or {}).get(
                        "solverd.field_queue")
                    if q is not None and q <= 0:
                        break
        watch.samples.clear()  # measurement window starts fresh
        t_end = time.monotonic() + window_s
        while time.monotonic() < t_end:
            sim.pump(0.4)
            watch.pump(0.1)
        win = watch.window("manager_centralized")
        if win is None:
            raise RuntimeError(
                f"no manager beacons in the window ({variant}, n={n})")
        first, last = win
        # tick count from the always-on tick_ms histogram (manager.
        # plan_ticks is a trace counter, gated behind JG_TRACE)
        tick_hist = _hist_delta(first, last, "tick_ms")
        rtt_hist = _hist_delta(first, last, "manager.plan_rtt_ms")
        ticks = max(tick_hist["count"] if tick_hist else 0, 1)
        row = {"variant": variant, "agents": n, "ticks": int(ticks),
               "sim_done_tasks": sim.done_count}
        if variant == "native":
            src = tick_hist
        else:
            src = rtt_hist
            row["responses_applied"] = 0 if rtt_hist is None \
                else rtt_hist["count"]
        if src is not None and src["count"] > 0:
            row["ms_per_tick_p50"] = round(hist_quantile(src, 0.5), 2)
            row["ms_per_tick_p95"] = round(hist_quantile(src, 0.95), 2)
            row["ms_per_tick_mean"] = round(src["sum"] / src["count"], 2)
            row["over_tick_budget"] = bool(
                (src["sum"] / src["count"]) > TICK_MS)
        wire = 0.0
        for name in ("bus.bytes_sent", "bus.bytes_received"):
            wire += _counter(last, name, topic="solver") \
                - _counter(first, name, topic="solver")
        row["solver_wire_bytes_per_tick"] = round(wire / ticks, 1)
        sd_win = watch.window("solverd")
        if sd_win is not None:
            f2, l2 = sd_win
            sd_ticks = max((l2.get("hists", {}).get("tick_ms", {})
                            .get("count", 0))
                           - (f2.get("hists", {}).get("tick_ms", {})
                              .get("count", 0)), 1)
            row["solverd"] = {
                "delta_agents_per_tick": round(
                    (_counter(l2, "solverd.delta_agents")
                     - _counter(f2, "solverd.delta_agents")) / sd_ticks, 1),
                "decode_bytes_per_tick": round(
                    (_counter(l2, "solverd.decode_bytes")
                     - _counter(f2, "solverd.decode_bytes")) / sd_ticks, 1),
                "scatter_lanes_per_tick": round(
                    (_counter(l2, "solverd.resident_scatter_lanes")
                     - _counter(f2, "solverd.resident_scatter_lanes"))
                    / sd_ticks, 1),
                "snapshots": int(
                    _counter(l2, "solverd.snapshots_applied")
                    - _counter(f2, "solverd.snapshots_applied")),
                "seq_gaps": int(_counter(l2, "solverd.seq_gaps")
                                - _counter(f2, "solverd.seq_gaps")),
            }
            ov = _hist_delta(f2, l2, "solverd.pipeline_overlap_ms")
            if ov is not None and ov["count"] > 0:
                row["solverd"]["pipeline_overlap_ms_mean"] = round(
                    ov["sum"] / ov["count"], 3)
        fo = _counter(last, "manager.solver_failovers") \
            - _counter(first, "manager.solver_failovers")
        if fo:
            row["solver_failovers_in_window"] = int(fo)
        return row
    finally:
        if sim is not None:
            sim.close()
        if watch is not None:
            watch.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=3)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in logs:
            log.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--counts", default="50,300,1000,3000")
    ap.add_argument("--variants", default="native,packed,json")
    ap.add_argument("--side", type=int, default=128,
                    help="map side; 128 puts the 3000-agent rung at ~18%% "
                         "density, the dense-warehouse regime TSWAP "
                         "targets")
    ap.add_argument("--window", type=float, default=20.0,
                    help="measurement window seconds per run")
    ap.add_argument("--settle", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tpu", action="store_true",
                    help="run solverd on the accelerator backend "
                         "(default: --cpu, the honest CI floor)")
    args = ap.parse_args()
    ensure_built()

    map_file = f"/tmp/crossover_{args.side}.map.txt"
    Path(map_file).write_text(
        "\n".join(["." * args.side] * args.side) + "\n")

    counts = [int(c) for c in args.counts.split(",")]
    variants = args.variants.split(",")
    rows = []
    for n in counts:
        for variant in variants:
            row = run_variant(variant, n, args.side, map_file,
                              args.window, args.settle, cpu=not args.tpu)
            rows.append(row)
            print(json.dumps(row), flush=True)

    by_n = {}
    for r in rows:
        by_n.setdefault(r["agents"], {})[r["variant"]] = r
    crossover = None
    wire_ratios = {}
    for n in sorted(by_n):
        v = by_n[n]
        if ("native" in v and "packed" in v
                and "ms_per_tick_p50" in v["native"]
                and "ms_per_tick_p50" in v["packed"]):
            if crossover is None and (v["packed"]["ms_per_tick_p50"]
                                      < v["native"]["ms_per_tick_p50"]):
                crossover = n
        if "packed" in v and "json" in v:
            jb = v["json"]["solver_wire_bytes_per_tick"]
            pb = v["packed"]["solver_wire_bytes_per_tick"]
            if pb > 0:
                wire_ratios[n] = round(jb / pb, 1)
    result = {
        "experiment": "live-fleet native vs solverd end-to-end ms/tick",
        "map": f"{args.side}x{args.side} empty",
        "tick_ms": TICK_MS,
        "solverd_backend": "accelerator" if args.tpu else "cpu",
        "note": ("native = manager tick_ms (plan+emit+adopt); "
                 "tpu = manager.plan_rtt_ms (request publish -> fresh "
                 "response applied).  Fleet is live: sim agents adopt "
                 "tasks, follow move_instructions, publish positions and "
                 "dones over busd."),
        "rows": rows,
        "crossover_agents": crossover,
        "json_over_packed_wire_ratio": wire_ratios,
    }
    print(json.dumps(result), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=2))
        md = ["| agents | native ms/tick p50 | tpu packed ms/tick p50 "
              "| winner | solver wire B/tick json | packed | ratio |",
              "|---|---|---|---|---|---|---|"]
        for n in sorted(by_n):
            v = by_n[n]
            nat = v.get("native", {}).get("ms_per_tick_p50")
            pk = v.get("packed", {}).get("ms_per_tick_p50")
            jw = v.get("json", {}).get("solver_wire_bytes_per_tick")
            pw = v.get("packed", {}).get("solver_wire_bytes_per_tick")
            win = "-" if nat is None or pk is None else (
                "tpu" if pk < nat else "native")
            md.append(f"| {n} | {nat} | {pk} | {win} | {jw} | {pw} | "
                      f"{wire_ratios.get(n, '-')} |")
        Path(str(args.out) + ".md").write_text("\n".join(md) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
