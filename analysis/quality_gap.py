"""Explain the 4096² est_ratio gap (VERDICT r4 item 4, second half).

At the flagship (10k/1024²) the solve BEATS the swap-free routing
estimate (est_ratio ~0.75–0.86); at 512 agents on 4096² it lands at 1.80.
Hypothesis: routing_est charges each task the distance from the NEAREST
agent start to its pickup (min over ALL agents) — at 10k agents that min
is a good proxy for whoever actually goes, but at 512 agents on 16.7M
cells agents are ~180 cells apart and tasks outnumber nearby agents, so
the ASSIGNED agent's journey is much longer than the nearest agent's.

This script recomputes the estimate ASSIGNMENT-AWARE: a greedy nearest-
pickup matching (the solver's own assignment policy, solver/mapd._assign)
over exact BFS start->pickup distances, then
  assigned_est = max_i  bfs(start_assigned(i) -> pickup_i) + bfs(pickup_i
                 -> delivery_i).
If assigned_est lands near the measured makespan, the 1.80 is assignment
geometry, not solve slack.

Usage: python analysis/quality_gap.py --out results/quality_gap_r05.json
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from p2p_distributed_tswap_tpu.models import scenarios  # noqa: E402
from p2p_distributed_tswap_tpu.ops.distance import (  # noqa: E402
    INF, distance_fields)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rung", default="extreme_lite_full")
    ap.add_argument("--measured-makespan", type=int, default=12782)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    scn = getattr(scenarios, args.rung.upper())
    grid, starts, tasks, cfg = scn.build(seed=0)
    starts = np.asarray(starts)
    tasks = np.asarray(tasks)
    n, t = len(starts), len(tasks)
    free_j = jnp.asarray(grid.free)

    starts_j = jnp.asarray(starts, jnp.int32)

    @functools.partial(jax.jit, static_argnums=3)
    def chunk_bfs_gather(free, goals, dl, r):
        # gather ON DEVICE: returning full (r, 16.7M) fields would drag
        # ~536 MB/chunk to the host and dominate the run
        f = distance_fields(free, goals,
                            max_rounds=cfg.max_sweep_rounds).reshape(r, -1)
        return f[:, starts_j], f[jnp.arange(r), dl]

    # exact BFS start->pickup for ALL (agent, task) pairs, and pickup->
    # delivery per task, from pickup-sourced fields (chunked to bound
    # device memory at 4096²)
    r = args.chunk
    d_sp = np.zeros((t, n), np.int64)   # task x agent
    d_pd = np.zeros(t, np.int64)
    for o in range(0, t, r):
        sel = np.clip(np.arange(o, o + r), 0, t - 1)
        sp, pd = chunk_bfs_gather(
            free_j, jnp.asarray(tasks[sel, 0], jnp.int32),
            jnp.asarray(tasks[sel, 1], jnp.int32), r)
        d_sp[sel] = np.asarray(sp)
        d_pd[sel] = np.asarray(pd)
        print(f"# fields {min(o + r, t)}/{t}", flush=True)

    # the solver's greedy policy: agents in slot order take the nearest
    # unused pickup (mirrors solver/mapd._assign's parallel chunked greedy
    # closely enough for an estimate)
    unused = np.ones(t, bool)
    assigned = np.full(t, -1)
    for a in range(n):
        cand = np.where(unused)[0]
        if not len(cand):
            break
        best = cand[np.argmin(d_sp[cand, a])]
        assigned[best] = a
        unused[best] = False

    m = assigned >= 0
    per_task_assigned = d_sp[np.arange(t)[m], assigned[m]] + d_pd[m]
    per_task_nearest = d_sp[m].min(axis=1) + d_pd[m]
    valid = per_task_assigned < int(INF)
    result = {
        "rung": scn.name, "agents": n, "tasks": t,
        "measured_makespan": args.measured_makespan,
        "routing_est_nearest_start": int(per_task_nearest[valid].max()),
        "routing_est_assigned": int(per_task_assigned[valid].max()),
        "assigned_over_measured": round(
            float(per_task_assigned[valid].max())
            / args.measured_makespan, 3),
        "measured_over_assigned": round(
            args.measured_makespan
            / float(per_task_assigned[valid].max()), 3),
        "mean_start_pickup_assigned": round(
            float(d_sp[np.arange(t)[m], assigned[m]][valid].mean()), 1),
        "mean_start_pickup_nearest": round(
            float(d_sp[m].min(axis=1)[valid].mean()), 1),
    }
    print(json.dumps(result, indent=2))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
