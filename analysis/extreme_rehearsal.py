"""EXTREME-shaped rehearsal on the virtual 8-device mesh (VERDICT r3 weak
#3: the 2-D sharded solver had only ever solved toy configs, so the EXTREME
memory plan rested on extrapolation).

Runs the 2-D (agents x tiles) sharded solver at EXTREME's *shape* scaled by
memory, not structure — thousands of agents, warehouse bands, EXTREME's
per-device replan chunk — TO COMPLETION with a device-side invariant fold
riding every step, and records per-device field residency (the arithmetic
the 840 GB EXTREME plan rests on) next to the measured run.

The host has ONE physical core, so the 8 virtual devices serialize:
ms/step here measures TOTAL WORK, not parallel wall-clock (same caveat as
analysis/sharded_steptime.py).  The point is capability + residency, not
speed.

Checkpoint/resume (VERDICT r4 item 5: the round-4 1024²-band attempt was
abandoned after 85 min with the rescue tool sitting unused in the repo):
``--checkpoint PATH`` saves the full sharded MapdState (solver/
checkpoint.py) plus a sidecar of loop latches every ``--checkpoint-every``
steps and at ``--max-seconds`` session end; ``--resume`` restores it —
skipping the multi-thousand-second prime burst, because the direction
fields ride the checkpoint — and continues bit-identically (the solver is
deterministic; tests/test_checkpoint.py).  A multi-hour band solve thus
runs as bounded sessions that survive kills, with wall-clock accumulated
across sessions in the sidecar.

Usage:
  python analysis/extreme_rehearsal.py --probe 8        # feasibility: time 8 steps
  python analysis/extreme_rehearsal.py                  # full certified run
  python analysis/extreme_rehearsal.py --out MULTICHIP_REHEARSAL_r04.json
  python analysis/extreme_rehearsal.py --checkpoint ck.npz --max-seconds 3600
  python analysis/extreme_rehearsal.py --checkpoint ck.npz --resume  # next session
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import os  # noqa: E402,F811

# The 8 virtual devices serialize on this host's ONE core, so a device
# thread can reach a collective minutes after its peers.  XLA CPU's
# rendezvous aborts the process after 40 s by default (rendezvous.cc
# "Termination timeout ... Exiting to ensure a consistent program
# state" — crashed the first full run); raise the limits far above the
# serialized skew.  Must be set before the CPU client exists.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_cpu_collective_timeout_seconds=7200"
    + " --xla_cpu_collective_call_terminate_timeout_seconds=7200").strip()

from p2p_distributed_tswap_tpu.parallel.virtual_mesh import pin_cpu_backend  # noqa: E402

pin_cpu_backend(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from p2p_distributed_tswap_tpu.core.config import SolverConfig  # noqa: E402
from p2p_distributed_tswap_tpu.core.grid import Grid  # noqa: E402
from p2p_distributed_tswap_tpu.core.sampling import start_positions_array  # noqa: E402
from p2p_distributed_tswap_tpu.core.tasks import TaskGenerator  # noqa: E402
from p2p_distributed_tswap_tpu.ops.distance import packed_cells  # noqa: E402
from p2p_distributed_tswap_tpu.parallel import sharded2d  # noqa: E402
from p2p_distributed_tswap_tpu.parallel.mesh import (  # noqa: E402
    AGENTS_AXIS,
    TILES_AXIS,
    agent_tile_mesh,
)
from p2p_distributed_tswap_tpu.solver import invariants, mapd  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=2048)
    ap.add_argument("--tasks", type=int, default=2048)
    ap.add_argument("--side", type=int, default=1024,
                    help="warehouse side (EXTREME is 4096)")
    ap.add_argument("--a-shards", type=int, default=2)
    ap.add_argument("--tiles", type=int, default=4)
    ap.add_argument("--replan-chunk", type=int, default=64,
                    help="EXTREME's 512 / 8 devices")
    ap.add_argument("--horizon", type=int, default=6000)
    ap.add_argument("--probe", type=int, default=0,
                    help="time N steps and exit (feasibility probe)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--checkpoint", default=None,
                    help="save resumable state here periodically")
    ap.add_argument("--checkpoint-every", type=int, default=256,
                    help="steps between checkpoint saves")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --checkpoint if it exists")
    ap.add_argument("--max-seconds", type=float, default=0,
                    help="end this session (with a checkpoint) after N s")
    args = ap.parse_args()

    grid = Grid.warehouse(args.side, args.side)
    n = args.agents
    cfg = SolverConfig(height=args.side, width=args.side, num_agents=n,
                       max_timesteps=args.horizon, record_paths=False,
                       replan_chunk=args.replan_chunk)
    starts = start_positions_array(grid, n, seed=0)
    tasks = TaskGenerator(grid, seed=1).generate_task_arrays(args.tasks)
    mesh = agent_tile_mesh(args.a_shards, args.tiles)
    specs = sharded2d.state_specs_2d()

    # per-device residency arithmetic (what EXTREME's 840 GB plan scales up)
    rows_dev = n // args.a_shards
    band_words = packed_cells(cfg.num_cells) // args.tiles
    dirs_dev_mb = rows_dev * band_words * 4 / 2**20
    sweep_dev_mb = (args.replan_chunk * (args.side // args.tiles)
                    * args.side * 4) / 2**20

    step_shard = jax.shard_map(
        functools.partial(sharded2d.sharded2d_mapd_step, cfg),
        mesh=mesh, in_specs=(specs, P(), P(TILES_AXIS, None)),
        out_specs=specs, check_vma=False)
    prime = jax.jit(jax.shard_map(
        functools.partial(sharded2d._prime_2d, cfg),
        mesh=mesh, in_specs=(specs, P(TILES_AXIS, None)), out_specs=specs,
        check_vma=False))

    # ONE program per loop iteration: step + invariant fold + makespan
    # latch + finished flag fused into a single jitted dispatch.  Separate
    # jitted programs over sharded operands interleave their collectives
    # across the serialized device threads in inconsistent order and
    # DEADLOCK the CPU rendezvous (observed live: worker CPU time frozen
    # mid-run); inside one program XLA orders every collective.
    @jax.jit
    def fused_iter(s, tasks, free, ok, done_t):
        prev = s.pos
        s = step_shard(s, tasks, free)
        ok = ok & invariants.step_invariants(cfg, prev, s.pos, free)
        done_t = jnp.where((done_t < 0) & mapd._finished(cfg, s),
                           s.t, done_t)
        return s, ok, done_t, mapd._finished(cfg, s)

    from p2p_distributed_tswap_tpu.solver.checkpoint import (
        load_extra, load_state, save_state)

    tasks_j = jnp.asarray(tasks, jnp.int32)
    to_mesh = functools.partial(
        jax.device_put,
        device=jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs))
    free_j = jax.device_put(jnp.asarray(grid.free),
                            NamedSharding(mesh, P(TILES_AXIS, None)))

    print(f"# config: {n} agents, {args.side}^2 warehouse, mesh "
          f"{args.a_shards}x{args.tiles}, replan_chunk {args.replan_chunk}",
          flush=True)
    print(f"# per-device: {rows_dev} field rows x {args.side//args.tiles}-row "
          f"band = {dirs_dev_mb:.0f} MB packed dirs, "
          f"{sweep_dev_mb:.0f} MB sweep transient", flush=True)

    steps = 0
    prime_s = 0.0     # one-time field prime (paid once, rides checkpoints)
    prior_s = 0.0     # loop wall-clock banked by previous sessions
    sessions = 1
    ok = jnp.bool_(True)
    done_t = jnp.int32(-1)
    if args.resume and args.checkpoint and os.path.exists(args.checkpoint):
        # state AND loop latches live in the same npz (save_state extra=),
        # atomically replaced as one file — a sidecar could tear from the
        # state on a mid-save kill
        s = to_mesh(load_state(args.checkpoint, cfg,
                               expected_num_tasks=len(tasks)))
        meta = load_extra(args.checkpoint)
        steps = int(meta["steps"])
        ok = jnp.bool_(bool(meta["invariants_ok"]))
        done_t = jnp.int32(int(meta["done_t"]))
        prime_s = float(meta["prime_s"])
        prior_s = float(meta["loop_s"])
        sessions = int(meta["sessions"]) + 1
        print(f"# resumed session {sessions} at t={steps} "
              f"({prior_s:.0f}s loop banked; prime burst skipped — the "
              f"fields ride the checkpoint)", flush=True)
    else:
        s = mapd.init_state(cfg, jnp.asarray(starts, jnp.int32), len(tasks))
        s = mapd._transitions(cfg, s, tasks_j)
        s = mapd._assign(cfg, s, tasks_j)
        s = to_mesh(s)
        t0 = time.perf_counter()
        s = prime(s, free_j)
        int(s.t)
        prime_s = time.perf_counter() - t0
        print(f"# prime burst: {prime_s:.1f}s", flush=True)

    def save_ckpt(elapsed_now):
        # .npz suffix so np.savez doesn't append one behind our back
        tmp = args.checkpoint + ".tmp.npz"
        save_state(tmp, s, extra={
            "steps": steps, "done_t": int(done_t),
            "invariants_ok": bool(ok), "sessions": sessions,
            "prime_s": prime_s, "loop_s": prior_s + elapsed_now})
        os.replace(tmp, args.checkpoint)

    t0 = time.perf_counter()
    if args.probe:
        for _ in range(args.probe):
            s, ok, done_t, _ = fused_iter(s, tasks_j, free_j, ok, done_t)
            steps += 1
        int(s.t)
        ms = 1000.0 * (time.perf_counter() - t0) / steps
        print(f"# probe: {ms:.0f} ms/step (1-core serialized), "
              f"invariants_ok={bool(ok)}")
        return

    FETCH_EVERY = 32
    session_steps = 0
    last_saved = steps
    finished = False
    while not finished and steps < cfg.max_timesteps + FETCH_EVERY:
        for _ in range(FETCH_EVERY):
            s, ok, done_t, fin = fused_iter(s, tasks_j, free_j, ok, done_t)
            steps += 1
            session_steps += 1
        finished = bool(fin)
        elapsed = time.perf_counter() - t0
        if steps % 512 == 0:
            print(f"# t={steps} elapsed={elapsed:.0f}s (session "
                  f"{sessions})", flush=True)
        # steps only lands on multiples of FETCH_EVERY, so compare against
        # the last save instead of a modulo that could never fire
        if args.checkpoint and steps - last_saved >= args.checkpoint_every:
            save_ckpt(elapsed)
            last_saved = steps
        if args.max_seconds and elapsed > args.max_seconds and not finished:
            save_ckpt(elapsed)
            print(json.dumps({
                "session": sessions, "paused_at_step": steps,
                "session_steps": session_steps,
                "session_s": round(elapsed, 1),
                "total_s": round(prime_s + prior_s + elapsed, 1),
                "resume": f"--checkpoint {args.checkpoint} --resume",
            }), flush=True)
            return
    elapsed = time.perf_counter() - t0
    if args.checkpoint:
        save_ckpt(elapsed)
    makespan = int(done_t)
    completed = bool(np.asarray(s.task_used).all()) and 0 < makespan
    result = {
        "experiment": "EXTREME-shaped 2-D mesh rehearsal (virtual 8-dev CPU)",
        "agents": n, "grid": f"{args.side}x{args.side} warehouse",
        "tasks": args.tasks,
        "mesh": f"{args.a_shards}x{args.tiles}",
        "replan_chunk": args.replan_chunk,
        "per_device_dirs_mb": round(dirs_dev_mb, 1),
        "per_device_sweep_mb": round(sweep_dev_mb, 1),
        "ms_per_step_serialized": round(
            1000.0 * (prior_s + elapsed) / max(steps, 1), 1),
        "makespan": makespan if completed else None,
        "completed": completed,
        "invariants_ok": bool(ok),
        "steps_run": steps,
        "prime_s": round(prime_s, 1),
        "wallclock_s": round(prime_s + prior_s + elapsed, 1),
        "sessions": sessions,
    }
    print(json.dumps(result), flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=2)


if __name__ == "__main__":
    main()
