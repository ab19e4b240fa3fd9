#!/usr/bin/env python3
"""Bring-up smoke on the chip: the main path, end to end, at real size.

    python chip_smoke.py               # one chip: flagship solve + served fleet
    python chip_smoke.py --chips 4     # four chips: 2x2 sharded solve only

Each phase runs as a child process, one after another, because only one
process can hold the chip: this parent never imports JAX.

- ``flagship``: ``scenarios.FLAGSHIP`` (10k agents, 1024^2 warehouse,
  seed 0) solved to completion by the one-program ``solver/mapd.py``
  solve; the recorded paths must pass ``bench._verify_paths`` and the
  compiled program must hold the Pallas sweep (``tpu_custom_call``).
- ``fleet``: the C++ runtime built from the committed sources, then a
  centralized ``--solver tpu`` fleet of the reference's 50 agents on its
  built-in 100x100 map.  At least 10 tasks must complete, ``solverd``
  must plan on a TPU and have answered plan requests, and the manager
  must never have failed over to native planning.
- ``sharded2d`` (``--chips 4`` only): 2048 agents on the 1024^2
  warehouse solved on a 2x2 (agents x tiles) mesh, bit-identical to the
  one-device solve on device 0 of the same process.

Numbers printed on the way are informational.  The last line is
``{"ok": true, "device": {...}}`` as the children reported the device;
any failed phase (no TPU among them) exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
RESULT_TAG = "chip_smoke.result "
PHASES = {1: ("flagship", "fleet"), 4: ("sharded2d",)}
PHASE_TIMEOUT_S = {"flagship": 600, "fleet": 540, "sharded2d": 1100}
FLEET_AGENTS = 50       # the reference's comfortable envelope
FLEET_TASKS_DONE = 10
# MULTICHIP_REHEARSAL_r05.json's instance (its makespan was 2224)
S2D_AGENTS, S2D_SIDE = 2048, 1024


# ---------------------------------------------------------------------------
# children (each holds the chip alone)
# ---------------------------------------------------------------------------

def _report(**fields) -> None:
    print(RESULT_TAG + json.dumps(fields), flush=True)


def _device(n_chips: int) -> dict:
    """Cache on, then the device as JAX reports it; exits 3 off the TPU."""
    from p2p_distributed_tswap_tpu import compile_cache
    from p2p_distributed_tswap_tpu.device import device_info

    cache_dir = compile_cache.enable()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX runs on {dev['platform']})",
              flush=True)
        sys.exit(3)
    if dev["count"] < n_chips:
        print(f"chip_smoke: {n_chips} chips needed, JAX sees "
              f"{dev['count']}", flush=True)
        sys.exit(3)
    print(f"chip_smoke: device {dev}, compile cache {cache_dir}", flush=True)
    return dev


def phase_flagship() -> None:
    dev = _device(1)
    import jax.numpy as jnp
    import numpy as np

    import bench
    from p2p_distributed_tswap_tpu.compile_cache import CacheCounter
    from p2p_distributed_tswap_tpu.models import scenarios
    from p2p_distributed_tswap_tpu.solver import mapd

    cache = CacheCounter()
    grid, starts, tasks, cfg = scenarios.FLAGSHIP.build(seed=0)
    args = (jnp.asarray(starts, jnp.int32), jnp.asarray(tasks, jnp.int32),
            jnp.asarray(grid.free))
    t0 = time.perf_counter()
    compiled = mapd._run_mapd_jit.lower(cfg, *args).compile()
    compile_s = time.perf_counter() - t0
    pallas = "tpu_custom_call" in compiled.as_text()
    t0 = time.perf_counter()
    final = compiled(*args)
    steps = int(final.t)  # the fetch waits for the device
    solve_s = time.perf_counter() - t0
    completed = bool(np.asarray(final.task_used).all()) \
        and 0 < steps <= cfg.max_timesteps
    inv_ok = completed and bench._verify_paths(
        cfg, grid, np.asarray(final.paths_pos[:steps]))
    print(f"chip_smoke: flagship {scenarios.FLAGSHIP.name}: makespan "
          f"{steps}, {1000.0 * solve_s / max(steps, 1):.3f} ms/step, "
          f"compile {compile_s:.1f} s, cache {cache.as_dict()}, "
          f"completed {completed}, invariants_ok {inv_ok}, "
          f"pallas {pallas}", flush=True)
    _report(passed=completed and inv_ok and pallas, device=dev,
            makespan=steps, ms_per_step=1000.0 * solve_s / max(steps, 1),
            compile_s=compile_s, cache=cache.as_dict(),
            completed=completed, invariants_ok=inv_ok, pallas=pallas)


def _counter(metrics: dict, name: str) -> float:
    return float((metrics.get("counters") or {}).get(name, 0.0))


def _data(frame) -> dict:
    d = (frame or {}).get("data")
    return d if isinstance(d, dict) else {}


def phase_fleet(log_dir: Path) -> None:
    """No JAX here: solverd, a grandchild, must be the one on the chip."""
    from p2p_distributed_tswap_tpu.runtime import buspool
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient
    from p2p_distributed_tswap_tpu.runtime.fleet import Fleet, ensure_built

    t0 = time.monotonic()
    try:
        ensure_built()
    except subprocess.CalledProcessError as e:
        print(f"chip_smoke: C++ build failed: {e}\n"
              f"{(e.stdout or b'')[-3000:].decode(errors='replace')}\n"
              f"{(e.stderr or b'')[-3000:].decode(errors='replace')}",
              flush=True)
        sys.exit(1)
    print(f"chip_smoke: cmake + ninja {time.monotonic() - t0:.1f} s",
          flush=True)
    port = buspool.free_port()
    fleet = watch = None
    mgr = {}
    stats = None
    try:
        t0 = time.monotonic()
        fleet = Fleet("centralized", num_agents=FLEET_AGENTS, port=port,
                      solver="tpu", log_dir=str(log_dir),
                      solverd_args=["--warm", str(FLEET_AGENTS)])
        print(f"chip_smoke: fleet up in {time.monotonic() - t0:.1f} s "
              f"(logs {log_dir})", flush=True)
        watch = BusClient(port=port, peer_id="chip-smoke")
        watch.subscribe("mapd.metrics")
        watch.subscribe("solver")
        time.sleep(2.0)
        fleet.command(f"tasks {2 * FLEET_TASKS_DONE}")
        t0 = time.monotonic()
        deadline = t0 + 300
        while time.monotonic() < deadline:
            d = _data(watch.recv(timeout=0.5))
            if d.get("type") == "metrics_beacon" \
                    and str(d.get("proc", "")).startswith("manager"):
                mgr = d.get("metrics") or {}
                if _counter(mgr, "manager.tasks_completed") \
                        >= FLEET_TASKS_DONE:
                    break
        done_s = time.monotonic() - t0
        watch.publish("solver", {"type": "stats_request"})
        deadline = time.monotonic() + 30
        while stats is None and time.monotonic() < deadline:
            d = _data(watch.recv(timeout=0.5))
            if d.get("type") == "stats_response":
                stats = d
    finally:
        if watch is not None:
            watch.close()
        if fleet is not None:
            fleet.quit()
    done = _counter(mgr, "manager.tasks_completed")
    failovers = _counter(mgr, "manager.solver_failovers")
    plans = int(((mgr.get("hists") or {}).get("manager.plan_rtt_ms")
                 or {}).get("count", 0))
    device = (stats or {}).get("device") or {}
    ticks = ((stats or {}).get("service") or {}).get("ticks", 0)
    natively = "planning natively" in (log_dir / "manager.log").read_text(
        errors="replace")
    died = [r for r in fleet.exit_summary
            if r["died_early"] and r["returncode"] != 0]
    print(f"chip_smoke: fleet {FLEET_AGENTS} agents on the built-in "
          f"100x100 map: {done:.0f} tasks done in {done_s:.1f} s, manager "
          f"applied {plans} plans, failovers {failovers:.0f}, solverd "
          f"answered {ticks} plan requests on {device}, died early "
          f"{[r['proc'] for r in died]}", flush=True)
    _report(passed=(done >= FLEET_TASKS_DONE and failovers == 0
                    and not natively and ticks >= 1 and plans >= 1
                    and device.get("platform") == "tpu" and not died),
            device=device, tasks_done=done, done_s=done_s,
            solverd_plan_responses=ticks, manager_plans_applied=plans,
            manager_failovers=failovers, died_early=len(died))


def phase_sharded2d() -> None:
    dev = _device(4)
    import jax
    import numpy as np

    from p2p_distributed_tswap_tpu.core.config import SolverConfig
    from p2p_distributed_tswap_tpu.core.grid import Grid
    from p2p_distributed_tswap_tpu.core.sampling import start_positions_array
    from p2p_distributed_tswap_tpu.core.tasks import TaskGenerator
    from p2p_distributed_tswap_tpu.parallel.mesh import agent_tile_mesh
    from p2p_distributed_tswap_tpu.parallel.sharded2d import (
        make_sharded2d_runner)
    from p2p_distributed_tswap_tpu.solver.mapd import solve_offline

    n, side = S2D_AGENTS, S2D_SIDE
    grid = Grid.warehouse(side, side)
    starts = start_positions_array(grid, n, seed=0)
    tasks = TaskGenerator(grid, seed=1).generate_task_arrays(n)
    cfg = SolverConfig(height=side, width=side, num_agents=n,
                       max_timesteps=4000, replan_chunk=64)
    devices = jax.devices()
    t0 = time.perf_counter()
    with jax.default_device(devices[0]):
        p1, s1, mk1 = solve_offline(grid, starts, tasks, cfg)
    one_s = time.perf_counter() - t0
    mesh = agent_tile_mesh(2, 2, devices=devices[:4])
    run = make_sharded2d_runner(cfg, mesh)
    t0 = time.perf_counter()
    final = run(starts, tasks, grid.free)
    mk2 = int(final.t)
    mesh_s = time.perf_counter() - t0
    on = {s.device.id for s in final.dirs.addressable_shards}
    p2 = np.asarray(final.paths_pos[:mk2])
    s2 = np.asarray(final.paths_state[:mk2])
    completed = bool(np.asarray(final.task_used).all()) \
        and 0 < mk2 <= cfg.max_timesteps
    same = (mk1 == mk2 and np.array_equal(p1, p2)
            and np.array_equal(s1, s2))
    print(f"chip_smoke: sharded2d 2x2, {n} agents on {side}^2: makespan "
          f"one-device {mk1} / mesh {mk2}, bit-identical {same}, "
          f"completed {completed}, field shards on devices {sorted(on)}, "
          f"wall one-device {one_s:.1f} s / mesh {mesh_s:.1f} s "
          f"(compile included)", flush=True)
    _report(passed=same and completed and len(on) == 4, device=dev,
            makespan=mk2, bit_identical=same, shard_devices=sorted(on),
            one_device_s=one_s, mesh_s=mesh_s)


# ---------------------------------------------------------------------------
# parent (never imports JAX)
# ---------------------------------------------------------------------------

def run_phase(name: str, log_dir: Path) -> dict | None:
    cmd = [sys.executable, str(Path(__file__).resolve()), "--phase", name,
           "--log-dir", str(log_dir)]
    print(f"chip_smoke: phase {name} ...", flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, cwd=str(ROOT), stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S[name])
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)  # the fleet's processes too
        out, _ = proc.communicate()
        print(out, end="", flush=True)
        print(f"chip_smoke: phase {name} timed out after "
              f"{PHASE_TIMEOUT_S[name]} s", flush=True)
        return None
    result = None
    for line in out.splitlines():
        if line.startswith(RESULT_TAG):
            result = json.loads(line[len(RESULT_TAG):])
        else:
            print(line, flush=True)
    wall = time.monotonic() - t0
    if proc.returncode != 0 or result is None or not result["passed"]:
        print(f"chip_smoke: phase {name} FAILED (exit {proc.returncode}, "
              f"{wall:.1f} s): {result}", flush=True)
        return None
    print(f"chip_smoke: phase {name} passed in {wall:.1f} s", flush=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1: flagship + served fleet; 4: the 2x2 sharded "
                         "solve against one device, and nothing else")
    ap.add_argument("--log-dir", default=str(ROOT / "results" / "chip_smoke"),
                    help="where the fleet phase keeps its process logs")
    ap.add_argument("--phase", choices=["flagship", "fleet", "sharded2d"],
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    log_dir = Path(args.log_dir)
    if args.phase is not None:
        sys.path.insert(0, str(ROOT))
        if args.phase == "flagship":
            phase_flagship()
        elif args.phase == "fleet":
            phase_fleet(log_dir / "fleet")
        else:
            phase_sharded2d()
        return 0

    if not (ROOT / "p2p_distributed_tswap_tpu").is_dir():
        print(f"chip_smoke: no p2p_distributed_tswap_tpu package next to "
              f"{Path(__file__).name}; run it from a checkout of the repo",
              flush=True)
        return 2
    device = None
    for name in PHASES[args.chips]:
        result = run_phase(name, log_dir)
        if result is None:
            return 1
        if name != "fleet":
            device = result["device"]
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
