"""Benchmark entry: the BASELINE.json config ladder.

Prints one informational JSON line per rung (stdout, one per line) and the
headline metric as the FINAL line — the driver parses one JSON line
(BENCH_r*.json); earlier lines are valid JSON too.

Headline: average wall-clock per MAPD timestep at the reference's own
comfortable configuration — 50 agents on the built-in 100x100 empty grid —
where the reference's centralized manager measured ~180 ms per planning step
(src/bin/centralized/manager.rs:564-567, DECENTRALIZED_ISSUES.md:36-42; see
BASELINE.md).  One timestep here includes everything the reference's step
includes and more: task assignment, replanning, the full TSWAP swap/rotation
conflict resolution, and movement for all agents.

Ladder rungs (models/scenarios.py): every completion-defined rung runs
the FULL fused solve (ms/step = total/steps, makespan reported, recorded
paths verified host-side); only the 4096^2 rungs — where completion is
undefined inside the horizon — measure a steady-state per-step window.
The north star (BASELINE.md): 10k agents on 1024^2, < 1 s/step on one
chip.

Every rung runs in a FRESH SUBPROCESS, once: the parent never imports
JAX, so each child holds the chip alone and keeps its compiles in the
persistent cache (p2p_distributed_tswap_tpu/compile_cache.py).  A rung
that fails ends the bench with a non-zero exit; there is no retry and no
fallback to another measurement.  Every rung record names the device it
ran on: ``device`` = ``{platform, kind, count}`` (device.py).

vs_baseline = reference_ms / our_ms for the reference rung (higher is
better); for other rungs it is target_ms / our_ms against the 1 s/step
north-star budget.

Solve-quality certification (VERDICT r2 item 1): every rung also reports
``invariants_ok`` — a device-side fold of per-transition MAPF legality
(vertex-disjointness, unit moves, free cells; solver/invariants.py, which
also documents why sanctioned mutual swaps are NOT flagged) so the headline
ms/step certifies a *correct* solve, not just throughput.  Full-solve rungs verify the recorded paths host-side;
step-window rungs fold the check through warmup and the BENCH_FULL
completion run (never inside the timed window).

Centralized-vs-decentralized rungs (VERDICT r2 item 2): the ``*-decent``
rungs run the same configs under the reference's radius-15 local-view
semantics — the TPU-scale analog of compare_path_metrics.py:33-106.
Round 4 adds three axes on top:
- ``*-decent-stale`` rungs (VERDICT r3 item 1): the reference's ACTUAL
  decentralized reality — views refreshed every 2 steps on decoupled
  cadences, TTL age-out, one-step non-atomic swap commits — where the
  makespan genuinely diverges from centralized;
- ``congested*`` rungs (VERDICT r3 item 2): 3k agents on a 256^2
  warehouse, dense enough that the mode comparison bites;
- ``extreme_lite_full`` (VERDICT r3 item 3): 4096^2 with a 20k horizon so
  completion is certified at the biggest single-chip grid;
- every rung reports ``makespan_lb``/``lb_ratio`` (a SOUND bound under
  goal-swap semantics — nearest-start visit times + bounded goal travel
  speed, see makespan_bounds — so lb_ratio >= 1 by construction) plus
  ``routing_est``/``est_ratio`` (the swap-free faithful-routing horizon,
  an estimate not a bound), plus ``completed`` split from
  ``invariants_ok``.

Env knobs: BENCH_RUNGS=comma list (see DEFAULT_RUNGS), BENCH_FULL=0 to
skip running large rungs to completion (default ON so committed BENCH
artifacts carry real makespans), BENCH_NO_LB=1 to skip the lower-bound
BFS, BENCH_SEEDS=comma list (default 0,1,2,3,4): headline rungs
(MULTISEED_RUNGS) run every seed and report mean±spread; other rungs run
seeds[0].

Fleetsim axis (ISSUE 7): unless BENCH_FLEETSIM=0 (or the C++ runtime is
unavailable), the headline also carries a ``fleetsim`` record — rated-load
fleet tasks/s and the p99 dispatch->claim wire phase from a scaled-down
``analysis/fleetsim.py`` run — so the BENCH trajectory tracks end-to-end
fleet health next to ms/step.

Field-engine axis (ISSUE 9): unless BENCH_FIELD=0, the headline carries a
``field_engine`` record — ms/field of a full fixpoint resweep vs the
bounded-region incremental repair (analysis/field_bench.py --quick) plus
the multi-field-kernel GO/NO-GO verdict — so dynamic-world repair cost
rides the BENCH trajectory too.

Audit axis (ISSUE 10): unless BENCH_AUDIT=0, the headline carries an
``audit`` record — digest µs per beacon body (flat fleet vs 8 tenant
slab rows, in a CPU child) and the live divergence-detection latency (corruption ->
confirmed roster divergence, in digest intervals) and drill cost from a scaled-down
``scripts/audit_smoke.py`` run — so the always-on audit cost stays on
the BENCH trajectory.

Mesh axis (ISSUE 13): unless BENCH_MESH=0, the headline carries a
``mesh`` record — flat vs 2-way vs 8-way virtual-mesh solverd rungs
(analysis/mesh_bench.py): tick/sweep ms per rung, per-device resident
bytes (the memory lever: peak HBM per device shrinks ~mesh-size), and
the bit_identical verdict — the first rungs of the sharded serving
trajectory.

Federation axis (ISSUE 14): unless BENCH_FEDERATION=0, the headline
carries a ``federation`` record — the live 2x1-region smoke
(scripts/federation_smoke.py): world-spanning tasks through two region
(manager) pairs, exact-once completion, handoff-protocol sent/acked
evidence, per-region ledgers drained.

HA axis (ISSUE 15): unless BENCH_HA=0, the headline carries an ``ha``
record — ledger1 replication cost in-process (record bytes + µs for a
256-task ledger, snapshot vs steady-state delta) and the live failover
(scripts/ha_smoke.py): SIGKILL the active mid-flight, takeover latency
in claim windows, replication stream bytes/s, digest-equal takeover +
exact-once verdicts.

Health axis (ISSUE 16): unless BENCH_HEALTH=0, the headline carries a
``health`` record — the watcher's evaluation cost in-process (full
engine beat over a synthetic 16-peer rollup: SLO judging, burn windows,
forecasters, ring append — µs/beat) and the live rehearsal
(scripts/health_smoke.py): zero false alerts on a clean run, the
diurnal-ramp forecast lead in evaluation intervals before the confirmed
breach, and the alert1 frames observed on the raw wire.

Replay axis (ISSUE 11): unless BENCH_REPLAY=0, the headline carries a
``replay`` record — replay FIDELITY of the committed CI capture
(results/captures/ci_small.capture.json re-driven open-loop through
``analysis/fleetsim.py --replay``): tasks/s drift vs the captured
original, outcome intactness (nothing lost/duplicated), and the final
ledger/view digests — so deterministic reproducibility stays measured
on the BENCH trajectory.

Bus axis (ISSUE 18): unless BENCH_BUS=0, the headline carries a ``bus``
record — same-host beacon throughput per hub core (beacons relayed per
busd CPU-second) with the shared-memory rings OFF vs ON on identical
pos1 traffic, plus the ring share and overflow-fallback count for the
shm rung.

Sector axis (ISSUE 19): unless BENCH_SECTOR=0, the headline carries a
``sector`` record — fresh-goal p50/p95 of the full field pipeline vs
the hierarchical sector planner on a 512^2 rung (analysis/
sector_bench.py --quick) plus the measured suboptimality bound, so the
corridor planner's latency win stays tracked on the BENCH trajectory.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

from p2p_distributed_tswap_tpu.device import device_info
from p2p_distributed_tswap_tpu.obs import trace

REFERENCE_STEP_MS = 180.0   # ~50 agents, 100x100 (BASELINE.md)
TARGET_STEP_MS = 1000.0     # north-star budget at scale (BASELINE.md)

# Rungs measured by the fused whole-solve program (ms/step = wall /
# makespan, recorded paths verified host-side).  One program per solve
# has no per-step dispatch (round 3 flagship: 126.6 ms/step stepwise vs
# 22.0 fused, same makespan).
FULL_SOLVE = {"ref", "small", "ref_decent", "medium", "medium_decent",
              "flagship", "flagship_decent", "ref_decent_stale",
              "medium_decent_stale", "flagship_decent_stale",
              "congested", "congested_decent", "congested_decent_stale"}
# rungs whose BENCH_FULL completion run is skipped: at 4096^2 the shortest
# paths alone exceed the 2000-step horizon, so "completion" is not defined
# at the default config — the rung certifies step legality + throughput only
NO_FULL = {"extreme", "extreme_lite"}
WARMUP_STEPS = 12
MEASURE_STEPS = 25

# Round-5 decision (VERDICT r4 item 7, numbers in SCALING.md): the
# fresh-r15 `*_decent` rungs are DEMOTED to test-only semantics — their
# outcomes are centralized-identical at every rung and every congestion
# seed (fresh per-step views make local decisions match global ones), so
# they added step-cost without an outcome axis; `*_decent_stale` (the
# reference's actual asynchronous reality, and cheaper to boot) carries
# the decentralized story.  The rungs remain runnable via BENCH_RUNGS.
DEFAULT_RUNGS = ("ref,small,medium,flagship,extreme_lite,"
                 "extreme_lite_full,"
                 "ref_decent_stale,medium_decent_stale,"
                 "flagship_decent_stale,"
                 "congested,congested_decent_stale")


def _rungs():
    from p2p_distributed_tswap_tpu.models import scenarios

    return {
        "ref": scenarios.REFERENCE_DEMO,
        "small": scenarios.SMALL,
        "medium": scenarios.MEDIUM,
        "flagship": scenarios.FLAGSHIP,
        "extreme": scenarios.EXTREME,
        "extreme_lite": scenarios.EXTREME_LITE,
        "extreme_lite_full": scenarios.EXTREME_LITE_FULL,
        "ref_decent": scenarios.REFERENCE_DEMO_DECENT,
        "medium_decent": scenarios.MEDIUM_DECENT,
        "flagship_decent": scenarios.FLAGSHIP_DECENT,
        "ref_decent_stale": scenarios.REFERENCE_DEMO_DECENT_STALE,
        "medium_decent_stale": scenarios.MEDIUM_DECENT_STALE,
        "flagship_decent_stale": scenarios.FLAGSHIP_DECENT_STALE,
        "congested": scenarios.CONGESTED,
        "congested_decent": scenarios.CONGESTED_DECENT,
        "congested_decent_stale": scenarios.CONGESTED_DECENT_STALE,
    }


def _verify_paths(cfg, grid, paths_pos) -> bool:
    """Host-side certification of a recorded full solve: every transition
    must be a legal collision-free MAPF step (solver/invariants.py lists
    the four checks; this is the numpy mirror for (T, N) path arrays)."""
    import numpy as np

    w = cfg.width
    free = np.asarray(grid.free).reshape(-1)
    for t in range(paths_pos.shape[0]):
        p = paths_pos[t]
        if len(np.unique(p)) != len(p) or not free[p].all():
            return False
        if t:
            q = paths_pos[t - 1]
            if (np.abs(p % w - q % w) + np.abs(p // w - q // w) > 1).any():
                return False
    return True


def makespan_bounds(grid, starts, tasks, cfg):
    """Sound makespan lower bound + swap-free routing estimate.

    ``lb`` — a TRUE lower bound on the makespan of any schedule the solver
    can produce, valid UNDER goal-swap semantics (VERDICT r4 item 4), from
    two mechanical facts of the kernel (solver/step.py):

    1. Task cells are visited PHYSICALLY: the agent standing on a task's
       pickup (or delivery) walked there from its own start at speed 1, so
       first-visit time of any cell >= BFS distance to the NEAREST agent
       start (one multi-source field, ops/distance.multi_source_field).
    2. Goals travel at a bounded speed: a goal only changes hands between
       ADJACENT agents (Rule-3 swap partner = occupant of the next path
       cell; Rule-4 rotation = one hop along a cycle of consecutive
       blockers), so per step a goal displaces at most ``swap_rounds``
       transfer hops + 1 holder move.  The delivery goal of task i is
       CREATED at the pickup cell (phase flip happens when its holder
       stands there), hence completion time
         t_done(i) >= first_visit(pickup_i) + ceil(bfs(pickup_i ->
                      delivery_i) / (swap_rounds + 1)).

    lb = max over tasks of max(d_near[delivery_i],
                               d_near[pickup_i] + ceil(d_pd_i / c)),
    also floored by ceil(T / N) (one completion per agent per step).
    ``lb_ratio = makespan / lb >= 1`` BY CONSTRUCTION at every rung.

    ``routing_est`` — the round-3/4 quantity, relabeled as the ESTIMATE it
    always was: bfs(pickup -> delivery) + Manhattan(nearest start ->
    pickup), max over tasks = the horizon of a swap-FREE faithful
    schedule.  est_ratio < 1 (flagship r4: 0.71) quantifies how much the
    goal-exchange machinery beats faithful per-task routing; est_ratio
    well above 1 (4096^2 r4: 1.80) flags assignment/queueing slack the
    per-task view cannot see.  It is NOT a bound and is reported as
    ``routing_est``/``est_ratio``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_distributed_tswap_tpu.ops.distance import (
        INF, distance_fields, multi_source_field)

    starts = np.asarray(starts)
    tasks = np.asarray(tasks)
    if tasks.size == 0:
        return 0, 0
    w = cfg.width
    sx, sy = starts % w, starts // w
    px, py = tasks[:, 0] % w, tasks[:, 0] // w
    c = cfg.swap_rounds + 1  # goal speed cap (transfer hops + holder move)

    free_j = jnp.asarray(grid.free)
    d_near = np.asarray(jax.jit(multi_source_field, static_argnums=2)(
        free_j, jnp.asarray(starts, jnp.int32),
        cfg.max_sweep_rounds)).reshape(-1)

    @functools.partial(jax.jit, static_argnums=2)
    def chunk_bfs(free, goals, r):
        f = distance_fields(free, goals, max_rounds=cfg.max_sweep_rounds)
        return f.reshape(r, -1)

    t = tasks.shape[0]
    r = min(cfg.replan_chunk, t)
    lb, est = 0, 0
    for o in range(0, t, r):
        sel = np.clip(np.arange(o, o + r), 0, t - 1)
        fields = chunk_bfs(free_j, jnp.asarray(tasks[sel, 1], jnp.int32), r)
        d_pd = np.asarray(fields[np.arange(r), tasks[sel, 0]])
        d_sp = (np.abs(sx[None, :] - px[sel, None])
                + np.abs(sy[None, :] - py[sel, None])).min(axis=1)
        np_, nd_ = d_near[tasks[sel, 0]], d_near[tasks[sel, 1]]
        valid = (d_pd < int(INF)) & (np_ < int(INF)) & (nd_ < int(INF))
        if valid.any():
            per_task = np.maximum(nd_[valid],
                                  np_[valid] + -(-d_pd[valid] // c))
            lb = max(lb, int(per_task.max()))
            est = max(est, int((d_pd[valid] + d_sp[valid]).max()))
    lb = max(lb, -(-t // cfg.num_agents))
    return lb, est


def bench_full_solve(scn, seed: int = 0, built=None, measure_only=False):
    """Full MAPD solve; ms/step averaged over the whole run.  The recorded
    paths are then certified host-side (_verify_paths).  Completion and
    per-transition legality are reported SEPARATELY: a horizon-exhausted
    but perfectly legal run must be attributable as "did not finish", not
    disguised as a collision (ADVICE r3).

    ``measure_only`` (the trace-off overhead re-measure) skips the warm run
    (the program is already compiled and warm from the primary measurement)
    and the host-side path verification — only ms/step is consumed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_distributed_tswap_tpu.solver import mapd

    grid, starts, tasks, cfg = built or scn.build(seed=seed)
    args = (cfg, jnp.asarray(starts, jnp.int32), jnp.asarray(tasks, jnp.int32),
            jnp.asarray(grid.free))
    if not measure_only:
        with trace.span("bench.compile_and_warm"):
            final = mapd._run_mapd_jit(*args)     # compile + warm run
            jax.block_until_ready(final)
    with trace.span("bench.measure_full_solve"):
        t0 = time.perf_counter()
        final = mapd._run_mapd_jit(*args)
        jax.block_until_ready(final)
        elapsed = time.perf_counter() - t0
    steps = int(final.t)
    assert steps > 0
    if measure_only:
        return 1000.0 * elapsed / steps, steps, None, None
    completed = bool(np.asarray(final.task_used).all()) and \
        steps <= cfg.max_timesteps
    inv_ok = _verify_paths(cfg, grid, np.asarray(final.paths_pos[:steps]))
    return 1000.0 * elapsed / steps, steps, completed, inv_ok


def bench_step_window(scn, seed: int = 0, no_full: bool = False, built=None):
    """Steady-state per-step time: one jitted ``mapd_step`` dispatched from a
    Python loop; WARMUP_STEPS absorb compilation and the initial
    field-computation burst, then MEASURE_STEPS are timed individually and
    averaged.  Path recording off — pure throughput (BASELINE.md measures
    step time).

    The measurement of the rungs outside FULL_SOLVE: the 4096^2 rungs,
    where completion is undefined at the default horizon and the one-
    program solve does not fit one chip (16.61 GB at extreme_lite_full,
    ROADMAP A4).  The state crosses the jit boundary undonated each step
    and dispatch overhead is accepted in the reported number."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from p2p_distributed_tswap_tpu.solver import invariants, mapd

    grid, starts, tasks, cfg = built or scn.build(seed=seed)
    cfg = dataclasses.replace(cfg, record_paths=False)
    starts_j = jnp.asarray(starts, jnp.int32)
    tasks_j = jnp.asarray(tasks, jnp.int32)
    free_j = jnp.asarray(grid.free)

    step = jax.jit(functools.partial(mapd.mapd_step, cfg))
    check = jax.jit(functools.partial(invariants.step_invariants, cfg))
    # initial assignment + wide-chunk field burst, off the clock.  At
    # EXTREME-class grids the burst runs as a host-driven per-chunk loop:
    # the one-fused-program prime crashes the TPU worker there
    # (mapd.host_prime_fields docstring).
    huge_grid = cfg.num_cells >= 2048 * 2048

    def prepare(tasks_in):
        if huge_grid:
            s, t = jax.jit(functools.partial(
                mapd.prepare_state_unprimed, cfg))(starts_j, tasks_in)
            return mapd.host_prime_fields(cfg, s, free_j), t
        return jax.jit(functools.partial(mapd.prepare_state, cfg))(
            starts_j, tasks_in, free_j)

    with trace.span("bench.prepare"):
        s, tasks_j = prepare(tasks_j)
    # invariant fold rides the warmup steps (and the completion run below),
    # NEVER the timed window — certification without distorting ms/step
    ok = jnp.bool_(True)
    with trace.span("bench.warmup", steps=WARMUP_STEPS):
        for _ in range(WARMUP_STEPS):
            prev = s.pos
            s = step(s, tasks_j, free_j)
            ok = ok & check(prev, s.pos, free_j)
        jax.block_until_ready(s)
    with trace.span("bench.measure_step_window", steps=MEASURE_STEPS):
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            s = step(s, tasks_j, free_j)
        jax.block_until_ready(s)
        elapsed = time.perf_counter() - t0
    makespan = None
    full = os.environ.get("BENCH_FULL", "1") != "0" and not no_full
    if full:
        # run to completion STEP-WISE as well.  Fetching the done flag
        # syncs the host with the device, so it is fetched only every
        # DONE_EVERY steps; the exact makespan comes from a
        # device-resident register that latches s.t at the first finished
        # step (steps past completion are harmless no-ops
        # for positions — tasks stay done, agents stay parked).
        DONE_EVERY = 8
        done = jax.jit(functools.partial(mapd._finished, cfg))
        mark = jax.jit(lambda s, dt: jnp.where(
            (dt < 0) & mapd._finished(cfg, s), s.t, dt))
        # the measured window's state still pins its (up to 4 GB at 4096^2)
        # field buffers; release them BEFORE preparing the completion
        # state or the chip holds three copies and OOMs (seen live at
        # extreme_lite_full, round 4)
        del s, prev
        s2, t2 = prepare(jnp.asarray(tasks, jnp.int32))
        done_t = jnp.int32(-1)
        finished = False
        while not finished:
            for _ in range(DONE_EVERY):
                prev = s2.pos
                s2 = step(s2, t2, free_j)
                ok = ok & check(prev, s2.pos, free_j)
                done_t = mark(s2, done_t)
            finished = bool(done(s2))
        makespan = int(done_t)
        import numpy as np
        completed = bool(np.asarray(s2.task_used).all()) and \
            makespan <= cfg.max_timesteps
    else:
        completed = None  # completion undefined / not attempted at this rung
    return 1000.0 * elapsed / MEASURE_STEPS, makespan, completed, bool(ok)


def _bench_trace_ctx_ns(iters: int = 2000) -> dict:
    """Micro-measure the per-hop cost of context propagation (ISSUE 5):
    one lifecycle-event emit (the ALWAYS-ON path: flight ring + registry)
    and one wire-context build+parse round.  Runs with the tracer forced
    off — the bench is called under JG_TRACE=1, and measuring the traced
    path would (a) time disk flushes instead of the always-on cost this
    guards and (b) pollute the rung's trace/events artifacts with
    thousands of synthetic events."""
    import time as _t

    from p2p_distributed_tswap_tpu.obs import events as _ev

    with trace.disabled():
        t0 = _t.perf_counter_ns()
        for k in range(iters):
            _ev.emit("bench.ctx", trace_id=k, hop=1, task_id=k)
        emit_ns = (_t.perf_counter_ns() - t0) / iters
    t0 = _t.perf_counter_ns()
    for k in range(iters):
        _ev.parse_tc({"tc": _ev.make_tc(k, 1)})
    wire_ns = (_t.perf_counter_ns() - t0) / iters
    return {"emit": round(emit_ns), "wire_tc": round(wire_ns)}


def run_rung(name: str, seed: int = 0) -> dict:
    scn = _rungs()[name]
    built = scn.build(seed=seed)  # one build serves measurement, LB and label
    grid = built[0]
    with trace.span("bench.rung", rung=name, seed=seed):
        if name in FULL_SOLVE:
            ms, steps, completed, inv_ok = bench_full_solve(scn, built=built)
            makespan = steps if completed else None
            measure = "full-solve"
        else:
            ms, makespan, completed, inv_ok = bench_step_window(
                scn, no_full=name in NO_FULL, built=built)
            if not completed:
                makespan = None
            measure = "step-window"
    # Tracing opt-in (JG_TRACE=1): re-measure with the tracer forced off so
    # the rung record carries the trace-on vs trace-off step-time delta —
    # instrumentation overhead regressions show up in the BENCH trajectory
    # instead of masquerading as solver slowdowns.  The trace itself lands
    # next to the BENCH artifacts ($BENCH_TRACE_DIR, default the JG_TRACE
    # dir).  Only the measured window matters for the delta: warm runs,
    # path verification, and completion passes are all skipped
    # (measure_only / no_full).
    trace_extra = {}
    if trace.enabled():
        with trace.disabled():
            if measure == "full-solve":
                ms_off = bench_full_solve(scn, built=built,
                                          measure_only=True)[0]
            else:
                ms_off = bench_step_window(scn, no_full=True, built=built)[0]
        tdir = os.environ.get("BENCH_TRACE_DIR", trace.trace_dir())
        tpath = trace.flush(os.path.join(
            tdir, f"bench-{name}-s{seed}.trace.jsonl"))
        trace_extra = {
            "trace_off_ms_per_step": round(ms_off, 4),
            "trace_overhead_pct": round(100.0 * (ms - ms_off) / ms_off, 2)
            if ms_off else None,
            "trace_file": tpath,
            # ISSUE 5: context-propagation overhead stays measured too —
            # ns per lifecycle event emit and per wire-context build/parse
            # (the per-message cost every traced hop pays; the <2%
            # step-time target is judged against the ~2 Hz x fleet rate)
            "trace_ctx_ns": _bench_trace_ctx_ns(),
        }
    # LB only when there is a makespan to ratio against: the BFS chunks are
    # real device work at the big grids — never spend them after a
    # measurement that cannot use the bound.
    lb = est = None
    if makespan is not None and os.environ.get("BENCH_NO_LB") != "1":
        _, starts, tasks, cfg = built
        lb, est = makespan_bounds(grid, starts, tasks, cfg)
    baseline = REFERENCE_STEP_MS if name.startswith("ref") else TARGET_STEP_MS
    return {
        "metric": f"mapd_step_wallclock_{scn.name}",
        "value": round(ms, 4),
        "unit": "ms/step",
        "vs_baseline": round(baseline / ms, 2),
        "makespan": makespan,
        "makespan_lb": lb,
        "lb_ratio": (round(makespan / lb, 3)
                     if makespan and lb else None),
        "routing_est": est,
        "est_ratio": (round(makespan / est, 3)
                      if makespan and est else None),
        "completed": completed,
        "invariants_ok": inv_ok,
        "agents": scn.num_agents,
        "grid": f"{grid.height}x{grid.width}",
        "mode": scn.mode,
        "measure": measure,
        "seed": seed,
        "device": device_info(),
        **trace_extra,
    }


def run_rung_subprocess(name: str, seeds=(0,)) -> list:
    """Run one rung once in a fresh process and return its per-seed
    records.  ALL requested seeds run in the SAME subprocess (one line per
    seed): the jitted solve is shape-identical across seeds, so 5 seeds
    pay ONE compile instead of five.  A child that fails, times out or
    prints no record ends the bench: exit 1 after its error record."""
    seeds = list(seeds)
    # one hour for the first seed plus half an hour per additional seed
    budget = 3600 + 1800 * (len(seeds) - 1)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--rung", name,
             "--seeds", ",".join(str(s) for s in seeds)],
            capture_output=True, text=True, timeout=budget,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
        err = "" if proc.returncode == 0 else \
            f"exit {proc.returncode}: {(proc.stderr or '')[-400:]}"
        stdout = proc.stdout or ""
    except subprocess.TimeoutExpired:
        err, stdout = f"timeout {budget}s", ""
    outs = []
    for line in stdout.strip().splitlines():
        try:
            out = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "metric" in out:
            outs.append(out)
    if err or len(outs) != len(seeds):
        print(json.dumps({"metric": f"mapd_step_wallclock_{name}",
                          "value": None, "unit": "ms/step",
                          "error": err or f"{len(outs)} of {len(seeds)} "
                                          "seeds reported"}), flush=True)
        sys.exit(1)
    return outs


def _aggregate_seeds(name: str, per_seed: list) -> dict:
    """Fold per-seed rung records into one mean±spread record (VERDICT r4
    item 6: no single-seed makespan quoted as a headline).  ms/step and
    vs_baseline are seed-means; makespan/lb_ratio carry mean, min, max and
    the per-seed lists so the spread is inspectable in the artifact."""
    ok = per_seed  # a failed seed has already ended the bench
    out = dict(ok[0])
    vals = [r["value"] for r in ok]
    out["value"] = round(sum(vals) / len(vals), 4)
    base = REFERENCE_STEP_MS if name.startswith("ref") else TARGET_STEP_MS
    out["vs_baseline"] = round(base / out["value"], 2)
    out["seeds"] = [r["seed"] for r in ok]
    out["ms_per_seed"] = vals
    mks = [r["makespan"] for r in ok if r.get("makespan")]
    if mks:
        out["makespan"] = round(sum(mks) / len(mks), 1)  # MEAN over seeds
        out["makespan_min"], out["makespan_max"] = min(mks), max(mks)
        out["makespans"] = mks
    lbr = [r["lb_ratio"] for r in ok if r.get("lb_ratio")]
    if lbr:
        out["lb_ratio"] = round(sum(lbr) / len(lbr), 3)
        out["lb_ratio_min"], out["lb_ratio_max"] = min(lbr), max(lbr)
    est = [r["est_ratio"] for r in ok if r.get("est_ratio")]
    if est:
        out["est_ratio"] = round(sum(est) / len(est), 3)
        out["est_ratio_min"], out["est_ratio_max"] = min(est), max(est)
    out["completed"] = all(r.get("completed") for r in ok)
    out["invariants_ok"] = all(r.get("invariants_ok") for r in ok)
    out.pop("seed", None)
    out.pop("makespan_lb", None)   # per-seed quantity; see lb_ratio spread
    out.pop("routing_est", None)
    return out


# Headline rungs run EVERY seed in BENCH_SEEDS (congestion showed per-seed
# makespan swings of ±20%+ at fixed config); the rest run seeds[0] only.
MULTISEED_RUNGS = {"ref", "medium", "flagship",
                   "ref_decent_stale", "medium_decent_stale",
                   "flagship_decent_stale"}


def run_fleetsim_axis() -> dict:
    """Scaled-down live-fleet SLO rung for the BENCH trajectory: rated
    tasks/s + p99 dispatch->claim wire ms from a small closed-loop
    fleetsim run (deterministic seed, relaxed scale).  Failures are
    recorded, never fatal — the solver rungs stay the headline."""
    import shutil
    import tempfile
    from pathlib import Path

    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    root = os.path.dirname(os.path.abspath(__file__))
    if not (BUILD_DIR / "mapd_bus").exists() \
            and (shutil.which("cmake") is None
                 or shutil.which("ninja") is None):
        return {"skipped": "C++ runtime unavailable"}
    out = Path(tempfile.mkdtemp(prefix="jg-bench-fleetsim-")) / "fs.json"
    cmd = [sys.executable, os.path.join(root, "analysis", "fleetsim.py"),
           "--agents", "40", "--side", "24", "--tick-ms", "250",
           "--settle", "12", "--window", "12", "--seed", "1",
           "--out", str(out)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=420,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        return {"error": "fleetsim timeout"}
    if not out.exists():
        return {"error": (proc.stderr or proc.stdout or "no output")[-300:]}
    try:
        rung = json.loads(out.read_text())["rungs"][0]
    except (json.JSONDecodeError, KeyError, IndexError) as e:
        return {"error": f"artifact parse: {e}"}
    sig = rung.get("signals") or {}
    return {
        "agents": rung.get("agents"),
        "tick_ms": rung.get("tick_ms"),
        "tasks_per_s": sig.get("fleet.tasks_per_s"),
        "completion_ratio": sig.get("fleet.completion_ratio"),
        "p99_dispatch_claim_wire_ms": sig.get("timeline.phase_p99_ms.wire"),
        "claim_wire_p99_ms": sig.get("sim.claim_wire_p99_ms"),
        "slo_ok": (rung.get("slo") or {}).get("ok"),
        "slo_failed": (rung.get("slo") or {}).get("failed"),
    }


def run_federation_axis() -> dict:
    """Federation rung for the BENCH trajectory (ISSUE 14): the live
    2x1-region smoke — world-spanning tasks through two (manager,
    solverd-less) region pairs, exact-once completion + handoff-protocol
    evidence.  Failures are recorded, never fatal."""
    import shutil

    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    root = os.path.dirname(os.path.abspath(__file__))
    if not (BUILD_DIR / "mapd_bus").exists() \
            and (shutil.which("cmake") is None
                 or shutil.which("ninja") is None):
        return {"skipped": "C++ runtime unavailable"}
    cmd = [sys.executable,
           os.path.join(root, "scripts", "federation_smoke.py"),
           "--log-dir", "/tmp/jg_bench_federation_logs"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=420,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        return {"error": "federation smoke timeout"}
    rec = None
    for line in (proc.stdout or "").splitlines():
        if line.startswith("federation smoke: "):
            try:
                rec = json.loads(line.split(": ", 1)[1])
            except json.JSONDecodeError:
                pass
    if rec is None:
        return {"error": (proc.stderr or proc.stdout or "no output")[-300:]}
    return {
        "regions": "2x1",
        "injected": rec.get("injected"),
        "cross_region_tasks": rec.get("cross_region_tasks"),
        "completed": rec.get("completed"),
        "handoffs_sent": rec.get("handoffs_sent"),
        "handoffs_acked": rec.get("handoffs_acked"),
        "views_drained": rec.get("views_drained"),
        "exact_once_ok": rec.get("ok"),
    }


def run_ha_axis() -> dict:
    """Control-plane HA rung (ISSUE 15): ledger1 replication cost
    in-process (encode+apply µs and record bytes for a 256-task ledger,
    snapshot vs small-churn delta) plus the LIVE takeover latency —
    kill the active mid-flight via scripts/ha_smoke.py and measure
    detect -> ha_takeover in claim windows.  Failures are recorded,
    never fatal."""
    import shutil
    import tempfile
    from pathlib import Path

    from p2p_distributed_tswap_tpu.runtime import ha
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    out: dict = {}
    root = os.path.dirname(os.path.abspath(__file__))
    n_tasks = 256
    reps = 50
    try:
        enc = ha.LedgerEncoder(incarnation=12345)
        rep = ha.LedgerReplica()
        tasks = [ha.LedgerTask(k + 1, k % 3, k, k + 7,
                               "" if k % 3 == 0 else f"peer{k:03d}")
                 for k in range(n_tasks)]
        snap = enc.encode_tick(1, 0, n_tasks + 1, tasks, {})
        out["snapshot_bytes"] = len(ha.encode_ledger(snap))
        rep.apply(snap)
        # steady-state delta: 4-task churn per beat (one done, one
        # dispatched, two state moves) — the common replication record
        t0 = time.perf_counter()
        seq = 1
        for r in range(reps):
            churn = list(tasks)
            del churn[r % n_tasks]
            base = (r * 4) % n_tasks
            for k in (base, (base + 11) % (n_tasks - 1)):
                t = churn[k]
                churn[k] = ha.LedgerTask(t.task_id, (t.state + 1) % 3,
                                         t.pickup, t.delivery, t.peer)
            churn.append(ha.LedgerTask(n_tasks + 2 + r, 1, 5, 9, "peerX"))
            rec = enc.encode_tick(seq + 1, 0, n_tasks + 3 + r, churn, {})
            seq += 1
            blob = ha.encode_ledger(rec)
            rep.apply(ha.decode_ledger(blob))
            if r == 0:
                out["delta_bytes"] = len(blob)
        out["delta_us_per_record"] = round(
            1e6 * (time.perf_counter() - t0) / reps, 1)
        out["ledger_tasks"] = n_tasks
        out["replica_divergences"] = rep.divergences
    except Exception as e:  # pragma: no cover - measurement best-effort
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    if not (BUILD_DIR / "mapd_bus").exists() \
            and (shutil.which("cmake") is None
                 or shutil.which("ninja") is None):
        out["live"] = {"skipped": "C++ runtime unavailable"}
        return out
    art = Path(tempfile.mkdtemp(prefix="jg-bench-ha-")) / "ha.json"
    cmd = [sys.executable, os.path.join(root, "scripts", "ha_smoke.py"),
           "--out", str(art), "--log-dir", "/tmp/jg_bench_ha_logs"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=420,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        out["live"] = {"error": "ha smoke timeout"}
        return out
    if not art.exists():
        out["live"] = {"error":
                       (proc.stderr or proc.stdout or "no output")[-300:]}
        return out
    rec = json.loads(art.read_text())
    claim_s = rec.get("claim_window_s") or 5.0
    lat = rec.get("takeover_latency_s")
    out["live"] = {
        "takeover_latency_s": lat,
        "takeover_claim_windows": (round(lat / claim_s, 2)
                                   if lat is not None else None),
        "replication_bytes_per_s": (rec.get("replication")
                                    or {}).get("bytes_per_s"),
        "digests_equal": rec.get("digests_equal"),
        "exact_once_ok": rec.get("ok"),
    }
    return out


def run_field_engine_axis() -> dict:
    """Field-engine rung for the BENCH trajectory (ISSUE 9): ms/field of
    a full resweep vs the bounded-region incremental repair at CI scale
    (analysis/field_bench.py --quick).  Failures are recorded, never
    fatal."""
    import tempfile
    from pathlib import Path

    root = os.path.dirname(os.path.abspath(__file__))
    out = Path(tempfile.mkdtemp(prefix="jg-bench-field-")) / "fe.json"
    cmd = [sys.executable,
           os.path.join(root, "analysis", "field_bench.py"),
           "--quick", "--out", str(out)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=600,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        return {"error": "field_bench timeout"}
    if not out.exists():
        return {"error": (proc.stderr or proc.stdout or "no output")[-300:]}
    try:
        doc = json.loads(out.read_text())
    except json.JSONDecodeError as e:
        return {"error": f"artifact parse: {e}"}
    r = doc.get("repair_vs_full") or {}
    return {
        "grid": r.get("grid"),
        "full_resweep_ms": r.get("full_resweep_ms_mean"),
        "repair_ms": r.get("repair_ms_mean"),
        "repair_speedup": r.get("speedup_vs_full"),
        "repair_fallbacks": r.get("repair_fallbacks"),
        "bit_identical": r.get("bit_identical_to_full_recompute"),
        "multi_field_verdict": (doc.get("multi_field") or {}).get(
            "verdict"),
    }


def run_sector_axis() -> dict:
    """Sector-planner rung (ISSUE 19): fresh-goal p50/p95 of the full
    field pipeline vs the hierarchical sector planner on a 512^2 rung
    (analysis/sector_bench.py --quick), plus the measured suboptimality
    bound.  Failures are recorded, never fatal."""
    import tempfile
    from pathlib import Path

    root = os.path.dirname(os.path.abspath(__file__))
    out = Path(tempfile.mkdtemp(prefix="jg-bench-sector-")) / "sector.json"
    cmd = [sys.executable,
           os.path.join(root, "analysis", "sector_bench.py"),
           "--quick", "--out", str(out)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=900,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        return {"error": "sector_bench timeout"}
    if not out.exists():
        return {"error": (proc.stderr or proc.stdout or "no output")[-300:]}
    try:
        doc = json.loads(out.read_text())
    except json.JSONDecodeError as e:
        return {"error": f"artifact parse: {e}"}
    fg = doc.get("fresh_goal") or {}
    row = (fg.get("sector") or [{}])[0]
    return {
        "grid": fg.get("grid"),
        "full_ms_p50": fg.get("full_ms_p50"),
        "full_ms_p95": fg.get("full_ms_p95"),
        "sector_s": row.get("s"),
        "sector_ms_p50": row.get("plan_ms_p50"),
        "sector_ms_p95": row.get("plan_ms_p95"),
        "speedup_p95": row.get("speedup_p95_vs_full"),
        "corridor_fraction": row.get("corridor_fraction"),
        "eps_max": (doc.get("epsilon") or {}).get("eps_max"),
        "eps_within_bound": (doc.get("epsilon") or {}).get(
            "within_bound"),
    }


def run_mesh_axis() -> dict:
    """Mesh-solverd rung (ISSUE 13): flat vs 2-way vs 8-way virtual-mesh
    tick/sweep ms + per-device resident bytes + the bit_identical
    verdict, via analysis/mesh_bench.py (fresh subprocesses — the
    virtual device count must be forced before each rung's jax CPU
    client exists).  Failures are recorded, never fatal."""
    import tempfile
    from pathlib import Path

    root = os.path.dirname(os.path.abspath(__file__))
    out = Path(tempfile.mkdtemp(prefix="jg-bench-mesh-")) / "mesh.json"
    cmd = [sys.executable, os.path.join(root, "analysis", "mesh_bench.py"),
           "--meshes", "1,2,8", "--agents", "16", "--side", "32",
           "--ticks", "10", "--no-replay", "--out", str(out)]
    try:
        # must exceed mesh_bench's own worst case (3 rungs x 1200 s
        # per-rung subprocess budget) or a slow-but-healthy run is
        # killed here and misreported as an error
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=3 * 1200 + 120,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        return {"error": "mesh_bench timeout"}
    if not out.exists():
        return {"error": (proc.stderr or proc.stdout or "no output")[-300:]}
    try:
        doc = json.loads(out.read_text())
    except json.JSONDecodeError as e:
        return {"error": f"artifact parse: {e}"}
    return {
        "bit_identical": doc.get("bit_identical"),
        "rungs": [{
            "mesh": r["mesh"],
            "devices": r["devices"],
            "tick_ms_p50": r["tick_ms_p50"],
            "sweep_chunk8_ms": r["sweep_chunk8_ms"],
            "resident_bytes_peak_shard": r["resident_bytes_peak_shard"],
        } for r in doc.get("rungs") or []],
    }


def run_replay_axis() -> dict:
    """Replay-fidelity rung (ISSUE 11): re-drive the committed CI
    capture open-loop and report drift vs the captured original —
    tasks/s delta, outcome intactness, final ledger/view digests.
    Failures are recorded, never fatal."""
    import shutil
    import tempfile
    from pathlib import Path

    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    root = os.path.dirname(os.path.abspath(__file__))
    capture = os.path.join(root, "results", "captures",
                           "ci_small.capture.json")
    if not os.path.exists(capture):
        return {"skipped": "no committed capture"}
    if not (BUILD_DIR / "mapd_bus").exists() \
            and (shutil.which("cmake") is None
                 or shutil.which("ninja") is None):
        return {"skipped": "C++ runtime unavailable"}
    out = Path(tempfile.mkdtemp(prefix="jg-bench-replay-")) / "rp.json"
    cmd = [sys.executable, os.path.join(root, "analysis", "fleetsim.py"),
           "--replay", capture, "--no-trace", "--out", str(out),
           "--log-dir", str(out.parent / "logs")]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=420,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        return {"error": "replay timeout"}
    if not out.exists():
        return {"error": (proc.stderr or proc.stdout or "no output")[-300:]}
    try:
        res = json.loads(out.read_text())["replay"]
    except (json.JSONDecodeError, KeyError) as e:
        return {"error": f"artifact parse: {e}"}
    digests = res.get("digests") or {}
    return {
        "capture": "results/captures/ci_small.capture.json",
        "expected": res.get("expected"),
        "completed": res.get("completed"),
        "missing": len(res.get("missing") or []),
        "done_dups": res.get("done_dups"),
        "outcome_ok": res.get("ok"),
        "tasks_per_s": res.get("window_tasks_per_s"),
        "orig_tasks_per_s": (res.get("baseline") or {}).get("tasks_per_s"),
        "tasks_per_s_drift_pct": (res.get("drift") or {}).get(
            "tasks_per_s_pct"),
        "ledger_digest": (digests.get("ledger") or {}).get("digest"),
        "view_digest": (digests.get("view") or {}).get("digest"),
        "audit_verdict": (res.get("audit") or {}).get("verdict"),
    }


def audit_digest_microbench() -> dict:
    """Digest-computation µs per audit beacon body: a flat 64-lane
    resident fleet vs 8 tenant slab rows of 64 lanes, against real
    resident state.  Runs in the ``--audit-digest`` child."""
    import numpy as np

    from p2p_distributed_tswap_tpu.core.grid import Grid
    from p2p_distributed_tswap_tpu.runtime import plan_codec as pcodec
    from p2p_distributed_tswap_tpu.runtime.solverd import (
        PlanService, Tenant, TenantSlab, TickRunner, audit_entries,
        audit_entries_tenant)

    out: dict = {"device": device_info()}
    lanes_per_fleet = 64
    reps = 50
    grid = Grid(np.ones((64, 64), np.bool_))
    # flat: a 64-lane resident fleet, digest body = mirror + device
    # pull + fields (what AuditBeacon computes per beat)
    runner = TickRunner(PlanService(grid, capacity_min=4), grid)
    enc = pcodec.PackedFleetEncoder(snapshot_every=64)
    fleet = [(f"ag{k:03d}", k, k + 1) for k in range(lanes_per_fleet)]
    assert runner.ingest({
        "type": "plan_request", "seq": 1, "codec": pcodec.CODEC_NAME,
        "caps": [pcodec.CODEC_NAME],
        "data": pcodec.encode_b64(enc.encode_tick(1, fleet))})
    audit_entries(runner.service, 1)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        audit_entries(runner.service, 1)
    out["flat_us_per_beacon"] = round(
        1e6 * (time.perf_counter() - t0) / reps, 1)
    out["flat_lanes"] = lanes_per_fleet
    # slab: 8 tenants x 64 lanes; one beat digests every tenant row
    svc2 = PlanService(grid, capacity_min=4)
    slab = TenantSlab(svc2, grid)
    slab._grow(8, lanes_per_fleet)
    rng = np.random.default_rng(0)
    slab.h_pos[:8, :lanes_per_fleet] = rng.integers(
        0, grid.num_cells, (8, lanes_per_fleet))
    slab.h_goal[:8, :lanes_per_fleet] = rng.integers(
        0, grid.num_cells, (8, lanes_per_fleet))
    slab.h_active[:8, :lanes_per_fleet] = True
    slab._upload()
    tenants = [Tenant(f"t{k}", k) for k in range(8)]
    for t in tenants:
        audit_entries_tenant(slab, t)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        for t in tenants:
            audit_entries_tenant(slab, t)
    out["slab8_us_per_beacon"] = round(
        1e6 * (time.perf_counter() - t0) / reps, 1)
    out["slab_tenants"] = 8
    out["slab_lanes_per_tenant"] = lanes_per_fleet
    return out


def run_audit_axis() -> dict:
    """Audit-plane rung (ISSUE 10): digest-computation µs per beacon
    body (``audit_digest_microbench``), plus live divergence-detection
    latency and drill cost from a scaled-down scripts/audit_smoke.py run.
    Both run in children on the CPU backend (this parent never imports
    JAX).  Failures are recorded, never fatal."""
    import shutil
    import tempfile
    from pathlib import Path

    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    out: dict = {}
    root = os.path.dirname(os.path.abspath(__file__))
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--audit-digest"],
            capture_output=True, text=True, timeout=420,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
        if proc.returncode != 0:
            raise RuntimeError(f"exit {proc.returncode}: "
                               f"{(proc.stderr or '')[-300:]}")
        out.update(json.loads(proc.stdout.strip().splitlines()[-1]))
    except Exception as e:  # noqa: BLE001 — axis must never kill BENCH
        out["microbench_error"] = f"{type(e).__name__}: {e}"
    if not (BUILD_DIR / "mapd_bus").exists() \
            and (shutil.which("cmake") is None
                 or shutil.which("ninja") is None):
        out["live"] = {"skipped": "C++ runtime unavailable"}
        return out
    art = Path(tempfile.mkdtemp(prefix="jg-bench-audit-")) / "audit.json"
    cmd = [sys.executable, os.path.join(root, "scripts", "audit_smoke.py"),
           "--out", str(art)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=420,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        out["live"] = {"error": "audit_smoke timeout"}
        return out
    if not art.exists():
        out["live"] = {"error": (proc.stderr or proc.stdout
                                 or "no output")[-300:]}
        return out
    try:
        doc = json.loads(art.read_text())
    except json.JSONDecodeError as e:
        out["live"] = {"error": f"artifact parse: {e}"}
        return out
    out["live"] = {
        "interval_s": doc.get("interval_s"),
        "clean_joins": (doc.get("clean") or {}).get("joins"),
        "detect_s": (doc.get("drill") or {}).get("detect_s"),
        "detect_intervals": (doc.get("drill") or {}).get(
            "detect_intervals"),
        "drill_requests": (doc.get("drill") or {}).get("requests"),
    }
    return out


def run_bus_axis() -> dict:
    """Bus-lane rung (ISSUE 18): beacons relayed per busd CPU-second
    (beacons/s/core — the hub's relay loop is the single core the
    fanout burns) with the shm rings off vs on, identical single-host
    pos1 traffic.  Failures are recorded, never fatal."""
    import base64
    import tempfile
    import threading

    from p2p_distributed_tswap_tpu.obs import registry as regmod
    from p2p_distributed_tswap_tpu.runtime import plan_codec
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient
    from p2p_distributed_tswap_tpu.runtime.buspool import free_port
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    busd = BUILD_DIR / "mapd_bus"
    if not busd.exists():
        return {"skipped": "C++ runtime unavailable"}

    def busd_cpu_s(pid: int) -> float:
        stat = open(f"/proc/{pid}/stat").read().rsplit(") ", 1)[1].split()
        return (int(stat[11]) + int(stat[12])) / os.sysconf("SC_CLK_TCK")

    def rung(shm: bool, window_s: float = 3.0) -> dict:
        lane_dir = tempfile.mkdtemp(prefix="jg-bench-bus-")
        env = dict(os.environ, JG_BUS_SHM="1" if shm else "0",
                   JG_BUS_SHM_DIR=lane_dir)
        port = free_port()
        proc = subprocess.Popen([str(busd), str(port)], env=env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.STDOUT)
        try:
            time.sleep(0.3)
            r_sub = regmod.Registry()
            sub = BusClient(port=port, peer_id="bench-sub",
                            registry=r_sub, shm=shm)
            pub = BusClient(port=port, peer_id="bench-pub",
                            registry=regmod.Registry(), shm=shm)
            for c in (sub, pub):
                end = time.monotonic() + 3
                while c.hub_caps is None and time.monotonic() < end:
                    c.recv(timeout=0.1)
            sub.subscribe("mapd.pos.0.0")
            time.sleep(0.2)
            beacon = {"type": "pos1", "data": base64.b64encode(
                plan_codec.encode_pos1(7, 42)).decode()}
            got = [0]
            stop = threading.Event()

            def drain():
                while not stop.is_set():
                    f = sub.recv(timeout=0.2)
                    if f and f.get("op") == "msg":
                        got[0] += 1

            t = threading.Thread(target=drain, daemon=True)
            t.start()
            cpu0, t0 = busd_cpu_s(proc.pid), time.monotonic()
            sent = 0
            while time.monotonic() - t0 < window_s:
                for _ in range(50):
                    pub.publish("mapd.pos.0.0", beacon)
                    sent += 1
                time.sleep(0.001)  # keep the rings drainable
            # let the tail flush before sampling the counters
            time.sleep(0.3)
            cpu = busd_cpu_s(proc.pid) - cpu0
            wall = time.monotonic() - t0
            stop.set()
            t.join(timeout=2)
            counters = r_sub.snapshot()["counters"]
            row = {
                "shm": shm,
                "window_s": round(wall, 2),
                "beacons_sent": sent,
                "beacons_delivered": got[0],
                "busd_cpu_s": round(cpu, 3),
                "beacons_per_s_per_core": round(got[0] / max(cpu, 1e-6)),
                "busd_cpu_us_per_beacon": round(1e6 * cpu
                                                / max(got[0], 1), 3),
            }
            if shm:
                row["shm_rx_frames"] = int(
                    counters.get("bus.shm_rx_frames", 0))
                pc = pub.registry.snapshot()["counters"]
                row["shm_tx_frames"] = int(pc.get("bus.shm_tx_frames", 0))
                row["shm_fallbacks"] = int(pc.get("bus.shm_fallbacks", 0))
            pub.close()
            sub.close()
            return row
        finally:
            proc.terminate()
            proc.wait(timeout=5)

    try:
        off = rung(False)
        on = rung(True)
    except Exception as e:  # noqa: BLE001 — axis must never kill BENCH
        return {"error": f"{type(e).__name__}: {e}"}
    out = {"rungs": [off, on]}
    if off.get("beacons_per_s_per_core") and on.get("beacons_per_s_per_core"):
        out["shm_speedup_per_core"] = round(
            on["beacons_per_s_per_core"] / off["beacons_per_s_per_core"], 2)
    return out


def run_health_axis() -> dict:
    """Health-plane rung (ISSUE 16): evaluation µs per watcher beat —
    the full engine pass (SLO judging + burn windows + forecasters +
    ring append) over a synthetic 16-peer rollup, measured in-process —
    plus the live forecast-lead / false-alert numbers from a
    scripts/health_smoke.py run.  Failures are recorded, never fatal."""
    import shutil
    import tempfile
    from pathlib import Path

    from p2p_distributed_tswap_tpu.obs import health as _health
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    out: dict = {}
    root = os.path.dirname(os.path.abspath(__file__))
    reps = 2000
    try:
        spec = {"name": "bench", "slos": [
            {"name": "backlog", "signal": "fleet.tasks_pending",
             "max": 40.0},
            {"name": "completion", "signal": "fleet.completion_ratio",
             "min": 0.3},
            {"name": "tick", "signal": "tick.p95_ms", "max": 400.0},
        ]}
        peers = {f"mgr-{k}": {"proc": "manager_centralized",
                              "mgr_tasks": {"dispatched": 40 + k,
                                            "completed": 30 + k,
                                            "pending": k},
                              "tick": {"p95_ms": 10.0 + k,
                                       "over_budget": 0}}
                 for k in range(16)}
        eng = _health.HealthEngine(spec=spec, interval=2.0)
        def beat(i):
            eng.observe({"beacons_ingested": i + 1, "peers": peers,
                         "fleet": {"tasks_pending": 5 + i % 7,
                                   "tasks_dispatched": 100 + i,
                                   "tasks_completed": 90 + i}},
                        now_ms=1000 + i * 2000,
                        signals={"fleet.tasks_pending": 5.0 + i % 7,
                                 "fleet.completion_ratio": 0.9,
                                 "tick.p95_ms": 12.0})
        beat(0)  # warm
        t0 = time.perf_counter()
        for i in range(1, reps + 1):
            beat(i)
        out["eval_us_per_beat"] = round(
            1e6 * (time.perf_counter() - t0) / reps, 1)
        out["slos"] = len(spec["slos"])
        out["rollup_peers"] = len(peers)
    except Exception as e:  # noqa: BLE001 — axis must never kill BENCH
        out["microbench_error"] = f"{type(e).__name__}: {e}"

    if not (BUILD_DIR / "mapd_bus").exists() \
            and (shutil.which("cmake") is None
                 or shutil.which("ninja") is None):
        out["live"] = {"skipped": "C++ runtime unavailable"}
        return out
    art = Path(tempfile.mkdtemp(prefix="jg-bench-health-")) / "health.json"
    cmd = [sys.executable,
           os.path.join(root, "scripts", "health_smoke.py"),
           "--out", str(art)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=420,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              cwd=root)
    except subprocess.TimeoutExpired:
        out["live"] = {"error": "health_smoke timeout"}
        return out
    if not art.exists():
        out["live"] = {"error": (proc.stderr or proc.stdout
                                 or "no output")[-300:]}
        return out
    try:
        doc = json.loads(art.read_text())
    except json.JSONDecodeError as e:
        out["live"] = {"error": f"artifact parse: {e}"}
        return out
    ramp = doc.get("ramp") or {}
    out["live"] = {
        "ok": doc.get("ok"),
        "clean_beats": (doc.get("clean") or {}).get("beats"),
        "clean_false_alerts": (doc.get("clean") or {}).get("alerts"),
        "forecast_lead_intervals": ramp.get("lead_intervals"),
        "forecast_eta_s": ((ramp.get("forecast") or {})
                           .get("forecast") or {}).get("eta_s"),
        "alerts_on_wire": ramp.get("alerts_on_wire"),
    }
    return out


def main():
    if sys.argv[1:] == ["--audit-digest"]:
        print(json.dumps(audit_digest_microbench()), flush=True)
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--rung":
        from p2p_distributed_tswap_tpu import compile_cache

        compile_cache.enable()
        trace.configure(proc=f"bench-{sys.argv[2]}")
        # --seeds a,b,c runs every seed in THIS process (one compile);
        # --seed N is the single-seed spelling
        seeds = [0]
        if len(sys.argv) >= 5 and sys.argv[3] in ("--seed", "--seeds"):
            seeds = [int(x) for x in sys.argv[4].split(",")]
        for sd in seeds:
            print(json.dumps(run_rung(sys.argv[2], sd)), flush=True)
        return
    rungs = os.environ.get("BENCH_RUNGS", DEFAULT_RUNGS)
    seeds = [int(s) for s in
             os.environ.get("BENCH_SEEDS", "0,1,2,3,4").split(",")]
    results = {}
    for name in [r.strip() for r in rungs.split(",") if r.strip()]:
        use = seeds if (name in MULTISEED_RUNGS and len(seeds) > 1) \
            else seeds[:1]
        per_seed = run_rung_subprocess(name, use)
        if len(use) > 1:
            for r in per_seed:
                print(json.dumps(r), flush=True)
            res = _aggregate_seeds(name, per_seed)
        else:
            res = per_seed[0]
        results[name] = res
        print(json.dumps(res), flush=True)
    # Headline LAST (the driver parses one JSON line): the reference rung,
    # with the flagship number attached when measured.
    head = dict(results.get("ref") or next(iter(results.values())))
    if "flagship" in results:
        head["flagship_ms_per_step"] = results["flagship"]["value"]
        head["flagship_under_1s_target"] = (
            results["flagship"]["value"] < TARGET_STEP_MS)
        head["flagship_makespan"] = results["flagship"].get("makespan")
        head["flagship_invariants_ok"] = results["flagship"].get(
            "invariants_ok")
    if os.environ.get("BENCH_FLEETSIM", "1") != "0":
        head["fleetsim"] = run_fleetsim_axis()
    if os.environ.get("BENCH_FIELD", "1") != "0":
        # field-engine axis (ISSUE 9): ms/field full vs incremental
        head["field_engine"] = run_field_engine_axis()
    if os.environ.get("BENCH_AUDIT", "1") != "0":
        # audit axis (ISSUE 10): digest µs/beacon + detection latency
        head["audit"] = run_audit_axis()
    if os.environ.get("BENCH_REPLAY", "1") != "0":
        # replay axis (ISSUE 11): fidelity of the committed CI capture
        head["replay"] = run_replay_axis()
    if os.environ.get("BENCH_MESH", "1") != "0":
        # mesh axis (ISSUE 13): flat vs 2/8-way virtual-mesh solverd
        head["mesh"] = run_mesh_axis()
    if os.environ.get("BENCH_SECTOR", "1") != "0":
        # sector axis (ISSUE 19): fresh-goal p50/p95 full vs sector
        head["sector"] = run_sector_axis()
    if os.environ.get("BENCH_FEDERATION", "1") != "0":
        # federation axis (ISSUE 14): 2x1 region pairs, exact-once
        # world-spanning completion + handoff evidence
        head["federation"] = run_federation_axis()
    if os.environ.get("BENCH_HA", "1") != "0":
        # HA axis (ISSUE 15): ledger1 replication cost + live takeover
        # latency in claim windows
        head["ha"] = run_ha_axis()
    if os.environ.get("BENCH_HEALTH", "1") != "0":
        # health axis (ISSUE 16): evaluation µs/beat + forecast lead
        head["health"] = run_health_axis()
    if os.environ.get("BENCH_BUS", "1") != "0":
        # bus axis (ISSUE 18): beacons/s/core, shm rings off vs on
        head["bus"] = run_bus_axis()
    print(json.dumps(head), flush=True)


if __name__ == "__main__":
    main()
