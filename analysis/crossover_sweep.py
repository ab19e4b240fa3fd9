"""Native-vs-solverd planning-time crossover sweep (VERDICT r4 item 1.ii).

The reference's centralized manager plans in ~180 ms at 50 agents and is
pinned to a 500 ms tick by it (manager.rs:564-567).  Our native C++
``tswap_step`` demolishes that wall at small N (0.04 ms at the fleet
envelope) — but its occupant scan is O(N^2) (cpp/common/tswap.hpp:33-38),
so it must blow past the tick at fleet sizes the TPU path shrugs at.  This
sweep measures both sides at N ∈ {50, 500, 2000, 5000} on a 256² map:

- native: ``mapd_tswap_bench`` (steady state, fields pre-warmed and never
  trimmed — strictly flattering to the native path; the real manager trims
  its cache at 512 fields and would also pay BFS recomputes);
- solverd: a synthetic plan_request driver over the real bus against the
  real daemon (``--warm N --capacity-min N``, accelerator backend),
  measuring the manager-visible request->response round-trip.

Output: one JSON with both curves and the crossover agent count, plus a
markdown table for the README.

Usage:
  python analysis/crossover_sweep.py --out results/crossover_r05.json
  python analysis/crossover_sweep.py --counts 50,500 --cpu   # smoke test
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient  # noqa: E402
from p2p_distributed_tswap_tpu.runtime.fleet import (  # noqa: E402
    BUILD_DIR, ensure_built)

SIDE = 256
TICK_MS = 500.0  # the reference's planning tick


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def native_ms(n: int, iters: int) -> dict:
    out = subprocess.run(
        [str(BUILD_DIR / "mapd_tswap_bench"), "--agents", str(n),
         "--side", str(SIDE), "--iters", str(iters)],
        capture_output=True, text=True, timeout=3600, check=True)
    return json.loads(out.stdout.strip())


def solverd_ms(n: int, rounds: int, warm_rounds: int, map_file: str,
               cpu: bool) -> dict:
    """Round-trip plan latency as the manager sees it: publish
    plan_request, wait for the matching plan_response."""
    import numpy as np

    port = _free_port()
    bus_p = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                             stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
    sd = None
    try:
        time.sleep(0.3)
        sd_cmd = [sys.executable, "-m",
                  "p2p_distributed_tswap_tpu.runtime.solverd",
                  "--port", str(port), "--map", map_file,
                  "--warm", str(n), "--capacity-min", str(n)]
        if cpu:
            sd_cmd.append("--cpu")
        sd = subprocess.Popen(sd_cmd, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
        lines = []
        import threading
        threading.Thread(target=lambda: [lines.append(l) for l in sd.stdout],
                         daemon=True).start()
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if any("solverd up" in l for l in lines):
                break
            if sd.poll() is not None:
                raise RuntimeError("solverd died:\n" + "".join(lines[-20:]))
            time.sleep(0.5)
        else:
            raise RuntimeError("solverd never became ready")
        warm_s = next((l for l in lines if "pre-warmed" in l), "").strip()
        warm_cut = len(lines)  # recompiles BEFORE this are the warm itself

        rng = np.random.default_rng(1)
        cells = rng.choice(SIDE * SIDE, size=2 * n, replace=False)
        agents = [{"peer_id": f"a{k}",
                   "pos": [int(cells[k]) % SIDE, int(cells[k]) // SIDE],
                   "goal": [int(cells[n + k]) % SIDE,
                            int(cells[n + k]) // SIDE]}
                  for k in range(n)]
        cli = BusClient(port=port, peer_id="sweepmgr")
        cli.subscribe("solver")
        time.sleep(0.3)

        def round_trip(seq: int):
            t0 = time.perf_counter()
            cli.publish("solver", {"type": "plan_request", "seq": seq,
                                   "agents": agents})
            end = time.monotonic() + 120
            while time.monotonic() < end:
                f = cli.recv(timeout=2.0)
                if (f and f.get("op") == "msg"
                        and (f.get("data") or {}).get("type")
                        == "plan_response"
                        and f["data"]["seq"] == seq):
                    return (1000.0 * (time.perf_counter() - t0),
                            f["data"].get("duration_micros", 0) / 1000.0)
            raise RuntimeError(f"no plan_response for seq {seq}")

        for k in range(warm_rounds):
            round_trip(k + 1)
        pairs = [round_trip(warm_rounds + k + 1) for k in range(rounds)]
        rtt = [p[0] for p in pairs]
        plan = [p[1] for p in pairs]  # daemon-side: parse + device step
        return {"agents": n,
                "ms_round_trip_avg": round(sum(rtt) / len(rtt), 3),
                "ms_round_trip_max": round(max(rtt), 3),
                "ms_daemon_plan_avg": round(sum(plan) / len(plan), 3),
                "warm_line": warm_s,
                "recompile_stalls_after_warm": sum(
                    1 for l in lines[warm_cut:] if "recompiled" in l)}
    finally:
        if sd is not None:
            sd.terminate()
        bus_p.terminate()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--counts", default="50,500,2000,5000,10000")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=15)
    ap.add_argument("--warm-rounds", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="solverd on CPU (smoke test; the artifact run "
                         "uses the accelerator)")
    args = ap.parse_args()
    ensure_built()
    subprocess.run(["ninja", "-C", str(BUILD_DIR), "mapd_tswap_bench"],
                   check=True, capture_output=True)

    map_file = str(Path("/tmp") / f"sweep_{SIDE}.map.txt")
    Path(map_file).write_text("\n".join(["." * SIDE] * SIDE) + "\n")

    counts = [int(c) for c in args.counts.split(",")]
    rows = []
    for n in counts:
        nat = native_ms(n, args.iters)
        sol = solverd_ms(n, args.rounds, args.warm_rounds, map_file,
                         args.cpu)
        row = {
            "agents": n,
            "native_ms_avg": nat["ms_per_step_avg"],
            "native_ms_max": nat["ms_per_step_max"],
            "native_over_tick": nat["ms_per_step_avg"] > TICK_MS,
            "solverd_ms_avg": sol["ms_round_trip_avg"],
            "solverd_ms_max": sol["ms_round_trip_max"],
            "solverd_daemon_plan_ms": sol["ms_daemon_plan_avg"],
            "solverd_over_tick": sol["ms_round_trip_avg"] > TICK_MS,
            "recompile_stalls_after_warm":
                sol["recompile_stalls_after_warm"],
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    crossover = next((r["agents"] for r in rows
                      if r["solverd_ms_avg"] < r["native_ms_avg"]), None)
    native_wall = next((r["agents"] for r in rows if r["native_over_tick"]),
                       None)
    # quadratic fit of the native curve (the occupant scan is O(N^2)):
    # projected N where native alone eats the whole 500 ms tick
    big = [r for r in rows if r["agents"] >= 1000]
    native_wall_projected = None
    if len(big) >= 2 and native_wall is None:
        import math
        c = (sum(r["native_ms_avg"] / r["agents"] ** 2 for r in big)
             / len(big))
        native_wall_projected = int(math.sqrt(TICK_MS / c))
    result = {
        "experiment": "native tswap_step vs solverd plan round-trip",
        "map": f"{SIDE}x{SIDE} empty",
        "tick_ms": TICK_MS,
        "backend": "cpu" if args.cpu else "accelerator",
        "note": ("solverd_daemon_plan_ms is the daemon-side figure "
                 "(request parse + one batched device step)."),
        "rows": rows,
        "crossover_agents": crossover,
        "native_blows_tick_at": native_wall,
        "native_blows_tick_at_projected": native_wall_projected,
    }
    print(json.dumps(result), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=2))
        md = ["| agents | native ms/plan | solverd ms/plan | within 500 ms tick |",
              "|---|---|---|---|"]
        for r in rows:
            who = ("both" if not r["native_over_tick"]
                   and not r["solverd_over_tick"] else
                   "solverd only" if r["native_over_tick"]
                   and not r["solverd_over_tick"] else
                   "native only" if not r["native_over_tick"] else "neither")
            md.append(f"| {r['agents']} | {r['native_ms_avg']:.2f} "
                      f"| {r['solverd_ms_avg']:.1f} | {who} |")
        Path(str(args.out) + ".md").write_text("\n".join(md) + "\n")


if __name__ == "__main__":
    main()
