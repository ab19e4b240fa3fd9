"""offline_ticks_stale: the decentralized fleet's ticks, replayed from a
snapshot, each tick's observation fetched while the next tick runs, and
judged by the stale reference.

Set-up as ``offline_ticks``'s: the cell's instance (``instance_seed``,
whatever ``--seed``), ``solver.mapd.prepare_state`` (``prime_s``),
``segment.start`` ticks, then a snapshot of the whole device state.  The
program is ``offline_ticks``'s (``mapd_step`` with the configuration's
stale knobs, so ``SolverConfig.stale_mode``), compiled with one change:
the step also returns its positions, goals and phases in buffers of their
own, so that they outlive the state donated to the next tick.

Window: restore the snapshot (off the clock), then ``segment.ticks``
ticks, again and again until ``--seconds`` have passed.  Tick ``j + 1``
is dispatched before tick ``j``'s positions, goals and phases are
fetched, so the host's fetch and dispatch overlap the device's next tick.
A tick ends when its positions, goals and phases are on the host, and is
timed from the end of the tick before it (the first tick of a replay,
from its dispatch).  On the v5e the fetch and dispatch of this cell's
state take ~1.5 ms a tick and move by up to 1 ms from run to run; done
one after the other with the tick, as ``offline_ticks`` does them, they
are a tenth of this cell's tick and spread its runs wider than the tick's
bound allows.  A tick still running when the window closes is neither
counted nor timed.

Judged afterwards, exactly: ``reference_stale.StaleSim`` solves the same
instance from t=0 with its own BFS fields, and every agent's position,
goal and phase at the snapshot and after every tick of the first replay
must equal it (``plan_mismatch``); every move is legal; every replay
equals the first (later replays are compared in the window, behind the
device's next tick, and not kept).

Two counters per tick of the first replay go to the ``fleet`` entry of
the layer dict: ``moved``, robots whose fetched position changed, and
``exchanges``, goal exchanges committed in the tick (two for a swap
pair, one for a push, one per ring member), from the reference's
decisions, which are the program's wherever ``plan_mismatch`` is 0.
"""

from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

import numpy as np

from harness import device as devmod
from harness import reference, reference_stale, world
from harness import trace as tracemod
from harness.drivers import offline_ticks
from harness.solver import solver_config


def _step_and_observe(cfg, s, tasks, free):
    from p2p_distributed_tswap_tpu.solver import mapd

    s = mapd.mapd_step(cfg, s, tasks, free)
    return s, (s.pos, s.goal, s.phase)


class Program(offline_ticks.Program):
    """``offline_ticks.Program`` whose tick leaves its observation on the
    device.  Tests break it by replacing :meth:`tick`."""

    def __init__(self, cfg, free, starts, orders):
        import jax

        super().__init__(cfg, free, starts, orders)
        self._step_fn = jax.jit(_step_and_observe, static_argnums=0,
                                donate_argnums=1)

    def tick(self, s):
        """Dispatch one tick: the new state, and the tick's positions,
        goals and phases, still on the device."""
        import jax

        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return self._step(s, self.tasks, self.free)

    def fetch(self, obs):
        import jax

        with jax.profiler.TraceAnnotation("bench.fetch"):
            return jax.device_get(obs)


def run(run, program=Program) -> dict:
    free = world.build_map(run.conf["map"])
    starts, orders = world.fleet(
        free, int(run.conf["agents"]), int(run.conf["orders"]),
        int(run.work["instance_seed"]))
    prog = program(solver_config(run), free, starts, orders)
    t0 = time.perf_counter()
    s = prog.prepare()
    prime_s = time.perf_counter() - t0
    prog.compile(s)
    a, k = int(run.work["segment"]["start"]), int(run.work["segment"]["ticks"])
    for _ in range(a):
        s, obs = prog.tick(s)
        prog.fetch(obs)
    snap = prog.snapshot(s)
    before = tuple(np.asarray(getattr(snap, f))
                   for f in offline_ticks.OBSERVED)
    s = prog.restore(s, snap)          # warms the restore program
    setup_s = time.perf_counter() - run.t_start
    run.log(f"set-up {setup_s:.3f} s (prime {prime_s:.3f} s, {a} ticks "
            f"to the snapshot)")

    # the first replay's observations; the others are compared with it
    # as they come and dropped, so the host's memory stays flat
    first, ticks, diff, tick_s, restore_s = [], [], [], [], []
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if run.trace else None
    end = time.perf_counter() + run.seconds
    done = False
    # a traced run traces one replay of the segment; the harness's own
    # garbage is not collected inside the window
    gc.collect()
    gc.disable()
    with tracemod.capture(logdir) if run.trace else contextlib.nullcontext():
        w0 = time.perf_counter()
        while not done:
            last = time.perf_counter()
            pending = None
            for j in range(k + 1):
                nxt = None
                if j < k:
                    s, nxt = prog.tick(s)
                if pending is not None:
                    obs = prog.fetch(pending)
                    t = time.perf_counter()
                    if t > end:
                        done = True
                        break
                    tick_s.append(t - last)
                    last = t
                    ticks.append(j - 1)     # ``pending`` was tick j - 1
                    if not restore_s:
                        first.append(obs)
                    elif any(not np.array_equal(x, y)
                             for x, y in zip(obs, first[j - 1])):
                        diff.append(j - 1)
                pending = nxt
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
    fleet = {}
    t0 = time.perf_counter()
    checks, failed_idx = judge(run, free, starts, orders, before, first,
                               diff, fleet)
    run.log(f"reference check {time.perf_counter() - t0:.3f} s")
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
                  "host_s": {"prime_s": prime_s}, "fleet": fleet},
    }


def judge(run, free, starts, orders, before, first, diff, fleet):
    """The checks, each [name, value, op, limit], and the segment's tick
    indices found wrong, from the first replay's observations and the
    ticks of later replays that differed from it; ``fleet`` gets the
    per-tick counters."""
    lim = run.work["limits"]
    pos = np.stack([before[0]] + [o[0] for o in first])
    illegal = reference.legality(free, pos)
    bad = {int(j) for j in np.flatnonzero(illegal[1:])} | set(diff)
    replay_diff = len(diff)

    sim = reference_stale.StaleSim(
        free, starts, orders,
        fields=lambda goals: reference.bfs_device(free, goals, block=256),
        **reference_stale.sim_options(run.conf["solver"]))
    a = int(run.work["segment"]["start"])
    for _ in range(a):
        sim.step()
    mismatch = offline_ticks._differ(sim, before)
    if mismatch:
        bad.add(0)
    for j, obs in enumerate(first):
        sim.step()
        d = offline_ticks._differ(sim, obs)
        if d:
            bad.add(j)
        mismatch += d
    run.log(f"stale reference: {sim.t} steps, {sim._nrows} fields; "
            f"{len(first)} ticks of {len(starts)} agents compared")
    fleet["moved"] = [int((pos[j + 1] != pos[j]).sum())
                      for j in range(len(first))]
    fleet["exchanges"] = sim.exchanges[a:a + len(first)]
    checks = [["illegal_moves", int(illegal.sum()), "<=", lim["illegal_moves"]],
              ["replay_diff", replay_diff, "<=", lim["replay_diff"]],
              ["plan_mismatch", mismatch, "<=", lim["plan_mismatch"]]]
    return checks, bad
