"""Flagship step-cost profile by ablation (VERDICT r2 item 6).

Written when no device profiler could be read on the chip, so the
phases are costed by subtraction (ROADMAP A0 replaces this with a trace
reduction).  Each variant runs the SAME pipelined
K-step loop (per-step dispatch, one value fetch at the end — the bench.py
pattern) with one phase ablated; the phase's cost is the difference from
the full step.  Trajectories diverge slightly once a phase is ablated
(stale fields change behavior), so differences are estimates of cost
structure, not exact decompositions — good enough to decide where a Pallas
kernel would (or would not) pay.

Variants:
  full         — the shipped mapd_step
  no_replan    — replan_fn = identity (fields go stale; sweeps ablated)
  no_swap      — swap_rounds = 0 (Rule 3/4 goal exchange ablated)
  kernel_only  — step_parallel alone on frozen fields (no transitions /
                 assignment / replan): the TSWAP rules + movement cascade
  dispatch     — jitted identity on the same state pytree: the sync floor

Usage: python analysis/step_profile.py [--rung flagship] [--steps 25]
Prints a markdown table; paste into SCALING.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp

from p2p_distributed_tswap_tpu.models import scenarios
from p2p_distributed_tswap_tpu.solver import mapd
from p2p_distributed_tswap_tpu.solver.step import step_parallel

WARMUP = 8


def _timed_loop(fn, s, steps, *args):
    for _ in range(WARMUP):
        s = fn(s, *args)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    for _ in range(steps):
        s = fn(s, *args)
    jax.block_until_ready(s)
    return 1000.0 * (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rung", default="flagship",
                    choices=["ref", "small", "medium", "flagship",
                             "extreme_lite"])
    ap.add_argument("--steps", type=int, default=25)
    args = ap.parse_args()

    scn = {"ref": scenarios.REFERENCE_DEMO, "small": scenarios.SMALL,
           "medium": scenarios.MEDIUM, "flagship": scenarios.FLAGSHIP,
           "extreme_lite": scenarios.EXTREME_LITE}[args.rung]
    grid, starts, tasks, cfg = scn.build(seed=0)
    cfg = dataclasses.replace(cfg, record_paths=False)
    starts_j = jnp.asarray(starts, jnp.int32)
    tasks_j = jnp.asarray(tasks, jnp.int32)
    free_j = jnp.asarray(grid.free)

    huge = cfg.num_cells >= 2048 * 2048
    if huge:
        s0, tasks_j = jax.jit(functools.partial(
            mapd.prepare_state_unprimed, cfg))(starts_j, tasks_j)
        s0 = mapd.host_prime_fields(cfg, s0, free_j)
    else:
        s0, tasks_j = jax.jit(functools.partial(mapd.prepare_state, cfg))(
            starts_j, tasks_j, free_j)
    jax.block_until_ready(s0.pos)

    rows = []

    def run(name, fn, *extra):
        ms = _timed_loop(fn, s0, args.steps, *extra)
        rows.append((name, ms))
        print(f"# {name}: {ms:.1f} ms/step", flush=True)
        return ms

    full = run("full", jax.jit(functools.partial(mapd.mapd_step, cfg)),
               tasks_j, free_j)
    no_replan = run(
        "no_replan",
        jax.jit(functools.partial(mapd.mapd_step, cfg,
                                  replan_fn=lambda c, s, f: s)),
        tasks_j, free_j)
    cfg_ns = dataclasses.replace(cfg, swap_rounds=0)
    no_swap = run("no_swap",
                  jax.jit(functools.partial(mapd.mapd_step, cfg_ns)),
                  tasks_j, free_j)

    def kernel(s, tasks, free):
        pos, goal, slot = step_parallel(cfg, s.pos, s.goal, s.slot, s.dirs)
        return s.replace(pos=pos, goal=goal, slot=slot, t=s.t + 1)

    kern = run("kernel_only", jax.jit(kernel), tasks_j, free_j)
    disp = run("dispatch", jax.jit(lambda s, tasks, free: s),
               tasks_j, free_j)

    print()
    print(f"| phase (ablation) | ms/step | share of full |")
    print(f"|---|---|---|")
    print(f"| full step | {full:.1f} | 100% |")
    print(f"| replan sweeps (full - no_replan) | {full - no_replan:.1f} "
          f"| {100 * (full - no_replan) / full:.0f}% |")
    print(f"| swap phase (full - no_swap) | {full - no_swap:.1f} "
          f"| {100 * (full - no_swap) / full:.0f}% |")
    print(f"| TSWAP kernel alone (rules + movement) | {kern:.1f} "
          f"| {100 * kern / full:.0f}% |")
    print(f"| transitions + assignment (no_replan - kernel) "
          f"| {no_replan - kern:.1f} | {100 * (no_replan - kern) / full:.0f}% |")
    print(f"| dispatch floor (jitted identity) | {disp:.1f} "
          f"| {100 * disp / full:.0f}% |")


if __name__ == "__main__":
    main()
