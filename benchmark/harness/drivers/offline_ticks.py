"""offline_ticks: replay a fixed segment of a solve's ticks from a snapshot.

Set-up: the cell's instance (``instance_seed``: the same starts and
orders in every run, whatever ``--seed``: relabelling the robots or the
orders changes TSWAP's tie-breaks, and tens of ticks on the work itself,
by up to 10% a tick on the LoRR cell), ``solver.mapd.prepare_state``
(the t=0 field burst, timed alone as ``prime_s``), ``segment.start``
ticks, then a snapshot of the whole device state.  Window: restore the
snapshot (off the clock), then ``segment.ticks`` ticks, again and again
until ``--seconds`` have passed.  Each tick is one dispatch of the
program's ``mapd_step`` with the working state donated, and ends when
its positions, goals and phases are on the host.  A tick still running
when the window closes is neither counted nor timed.

Judged afterwards against the reference, exactly: ``reference.Sim``
solves the same instance from t=0 with its own BFS fields, and every
agent's position, goal and phase at the snapshot and after every tick
of the first replay must equal it (``plan_mismatch``); every move is
legal (``reference.legality``); every replay equals the first.
"""

from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

import numpy as np

from harness import device as devmod
from harness import reference, world
from harness import trace as tracemod
from harness.solver import solver_config

OBSERVED = ("pos", "goal", "phase")


class Program:
    """The system under test, compiled for one cell.  Tests break it by
    replacing :meth:`tick`."""

    def __init__(self, cfg, free, starts, orders):
        import jax
        import jax.numpy as jnp
        from p2p_distributed_tswap_tpu.solver import mapd

        self.cfg = cfg
        self.free = jnp.asarray(free)
        self.starts = jnp.asarray(starts)
        self.orders = jnp.asarray(orders)
        self._prep = jax.jit(mapd.prepare_state, static_argnums=0).lower(
            cfg, self.starts, self.orders, self.free).compile()
        self._step_fn = jax.jit(mapd.mapd_step, static_argnums=0,
                                donate_argnums=1)

    def prepare(self):
        import jax

        s, self.tasks = self._prep(self.starts, self.orders, self.free)
        jax.block_until_ready(s)
        return s

    def compile(self, s):
        """The step and the snapshot copy, for the state's shapes."""
        import jax
        import jax.numpy as jnp

        self._step = self._step_fn.lower(self.cfg, s, self.tasks,
                                         self.free).compile()
        self._copy = jax.jit(
            lambda x: jax.tree.map(jnp.copy, x)).lower(s).compile()

    def tick(self, s):
        import jax

        with jax.profiler.TraceAnnotation("bench.dispatch"):
            s = self._step(s, self.tasks, self.free)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            obs = jax.device_get((s.pos, s.goal, s.phase))
        return s, obs

    def snapshot(self, s):
        import jax

        snap = self._copy(s)
        jax.block_until_ready(snap)
        return snap

    def restore(self, s, snap):
        """A fresh working copy of ``snap``; ``s`` is freed first, so the
        chip holds two states at most (donating ``s`` to the copy does not
        alias, and three 5.24 GB states leave 1 GB free)."""
        import jax

        for leaf in jax.tree.leaves(s):
            leaf.delete()
        return self.snapshot(snap)


def run(run, program=Program) -> dict:
    free = world.build_map(run.conf["map"])
    starts, orders = world.fleet(
        free, int(run.conf["agents"]), int(run.conf["orders"]),
        int(run.work["instance_seed"]))
    cfg = solver_config(run)
    prog = program(cfg, free, starts, orders)
    t0 = time.perf_counter()
    s = prog.prepare()
    prime_s = time.perf_counter() - t0
    prog.compile(s)
    a, k = int(run.work["segment"]["start"]), int(run.work["segment"]["ticks"])
    for _ in range(a):
        s, _ = prog.tick(s)
    snap = prog.snapshot(s)
    before = tuple(np.asarray(getattr(snap, f)) for f in OBSERVED)
    s = prog.restore(s, snap)          # warms the restore program
    setup_s = time.perf_counter() - run.t_start
    run.log(f"set-up {setup_s:.3f} s (prime {prime_s:.3f} s, {a} ticks "
            f"to the snapshot)")

    replays, tick_s, restore_s = [], [], []
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if run.trace else None
    end = time.perf_counter() + run.seconds
    window_s = 0.0
    done = False
    # a traced run traces one replay of the segment; the harness's own
    # garbage is not collected inside the window
    gc.collect()
    gc.disable()
    with tracemod.capture(logdir) if run.trace else contextlib.nullcontext():
        w0 = time.perf_counter()
        while not done:
            obs_rep = []
            replays.append(obs_rep)
            for _ in range(k):
                t0 = time.perf_counter()
                s, obs = prog.tick(s)
                t1 = time.perf_counter()
                if t1 > end:
                    done = True
                    break
                tick_s.append(t1 - t0)
                obs_rep.append(obs)
            done = done or run.trace
            if not done:
                t0 = time.perf_counter()
                s = prog.restore(s, snap)
                restore_s.append(time.perf_counter() - t0)
        window_s = time.perf_counter() - w0
    gc.enable()
    if restore_s:
        run.log(f"snapshot restores (off the clock): {len(restore_s)}, "
                f"mean {1e3 * np.mean(restore_s):.3f} ms")
    peak = devmod.memory_peak_bytes(int(run.entry["chips"]))
    del s, snap
    prog = None
    gc.collect()

    events = None
    if run.trace:
        events = tracemod.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
    t0 = time.perf_counter()
    checks, failed_idx = judge(run, free, starts, orders, before, replays)
    run.log(f"reference check {time.perf_counter() - t0:.3f} s")
    ticks = [j for rep in replays for j in range(len(rep))]
    ms = 1e3 * np.asarray(tick_s)
    return {
        "e2e": {"tick_ms": float(ms.sum() / max(len(ms), 1)),
                "tick_p95_ms": float(np.percentile(ms, 95)) if len(ms)
                else float("nan"),
                "setup_s": setup_s},
        "attempted": len(ticks),
        "failed": sum(1 for j in ticks if j in failed_idx),
        "checks": checks,
        "memory_peak_bytes": peak,
        "layer": {"trace": events, "window_s": window_s,
                  "host_s": {"prime_s": prime_s}},
    }


def judge(run, free, starts, orders, before, replays):
    """The checks, each [name, value, op, limit], and the segment's
    tick indices found wrong."""
    lim = run.work["limits"]
    first = replays[0]
    pos = np.stack([before[0]] + [o[0] for o in first])
    illegal = reference.legality(free, pos)
    bad = {int(j) for j in np.flatnonzero(illegal[1:])}
    replay_diff = 0
    for rep in replays[1:]:
        for j, o in enumerate(rep):
            if any(not np.array_equal(x, y) for x, y in zip(o, first[j])):
                replay_diff += 1
                bad.add(j)

    sim = reference.Sim(
        free, starts, orders,
        fields=lambda goals: reference.bfs_device(free, goals, block=256),
        **reference.sim_options(run.conf["solver"]))
    for _ in range(int(run.work["segment"]["start"])):
        sim.step()
    mismatch = _differ(sim, before)
    if mismatch:
        bad.add(0)
    for j, obs in enumerate(first):
        sim.step()
        d = _differ(sim, obs)
        if d:
            bad.add(j)
        mismatch += d
    run.log(f"reference: {sim.t} steps, {sim._nrows} fields; "
            f"{len(first)} ticks of {len(starts)} agents compared")
    checks = [["illegal_moves", int(illegal.sum()), "<=", lim["illegal_moves"]],
              ["replay_diff", replay_diff, "<=", lim["replay_diff"]],
              ["plan_mismatch", mismatch, "<=", lim["plan_mismatch"]]]
    return checks, bad


def _differ(sim, obs) -> int:
    """Agents whose (position, goal, phase) differ from the reference's."""
    pos, goal, phase = (np.asarray(x, dtype=np.int64) for x in obs)
    return int(((pos != sim.pos) | (goal != sim.goal)
                | (phase != sim.phase)).sum())
