"""offline_solves: complete solves of small batches, back to back.

Set-up: the cell's ``cycle`` batches (from ``instance_seed``: the same
batches in every run, so every seed does the same work) put on the
device, the program's one-program solve (``solver.mapd._run_mapd_jit``)
compiled and run once.  Window: one solve per batch in turn, in an order
drawn from ``--seed``; a solve
ends when its makespan and recorded paths are on the host.  A solve
still running when the window closes is neither counted nor timed.  A
traced run traces one cycle of solves.

Judged afterwards: each batch solved in the window against the exact
reference solve (``reference.Sim``): makespan, every recorded position
and state; every repeat of a batch equal to its first solve.
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


class Program:
    """The system under test, compiled for one cell.  Tests break it by
    replacing :meth:`solve`."""

    def __init__(self, cfg, free, batches):
        import jax
        import jax.numpy as jnp
        from p2p_distributed_tswap_tpu.solver import mapd

        self.cfg = cfg
        self.free = jnp.asarray(free)
        self.batches = [(jnp.asarray(s), jnp.asarray(o)) for s, o in batches]
        s0, o0 = self.batches[0]
        self._solve = mapd._run_mapd_jit.lower(cfg, s0, o0,
                                               self.free).compile()

    def solve(self, b):
        import jax

        starts, orders = self.batches[b]
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            final = self._solve(starts, orders, self.free)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            t, pp, ps = jax.device_get((final.t, final.paths_pos,
                                        final.paths_state))
        t = int(t)
        return t, pp[:t], ps[:t]


def run(run, program=Program) -> dict:
    free = world.build_map(run.conf["map"])
    cycle = int(run.work["cycle"])
    base = int(run.work["instance_seed"])
    batches = [world.fleet(free, int(run.conf["agents"]),
                           int(run.conf["orders"]), base + b)
               for b in range(cycle)]
    turn = world.order(run.seed, cycle)
    cfg = solver_config(run)
    prog = program(cfg, free, batches)
    prog.solve(int(turn[0]))             # warm: first execution
    setup_s = time.perf_counter() - run.t_start
    run.log(f"set-up {setup_s:.3f} s")

    # each batch's first plan is kept for the reference; a repeat is
    # compared with it at once and dropped (held plans would grow the
    # heap through the window)
    first, solved, solve_s = {}, [], []
    repeat_diff, bad = 0, set()
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if run.trace else None
    with tracemod.capture(logdir) if run.trace else contextlib.nullcontext():
        w0 = time.perf_counter()
        end = w0 + run.seconds
        i = 0
        while not (run.trace and i == cycle):   # a traced run: one cycle
            b = int(turn[i % cycle])
            t0 = time.perf_counter()
            out = prog.solve(b)
            t1 = time.perf_counter()
            if t1 > end:
                break
            solve_s.append(t1 - t0)
            solved.append(b)
            if b not in first:
                first[b] = out
            elif not _same(out, first[b]):
                repeat_diff += 1
                bad.add(b)
            i += 1
        window_s = time.perf_counter() - w0
    peak = devmod.memory_peak_bytes(int(run.entry["chips"]))
    prog = None
    gc.collect()
    events = None
    if run.trace:
        events = tracemod.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)

    checks = judge(run, free, batches, first, bad)
    checks.insert(1, ["repeat_diff", repeat_diff, "<=",
                      run.work["limits"]["repeat_diff"]])
    ms = 1e3 * np.asarray(solve_s)
    return {
        "e2e": {"solve_ms": float(ms.sum() / max(len(ms), 1)),
                "setup_s": setup_s},
        "attempted": len(solved),
        "failed": sum(1 for b in solved if b in bad),
        "checks": checks,
        "memory_peak_bytes": peak,
        "layer": {"trace": events, "window_s": window_s},
    }


def _same(a, b) -> bool:
    return a[0] == b[0] and np.array_equal(a[1], b[1]) \
        and np.array_equal(a[2], b[2])


def judge(run, free, batches, first, bad):
    """Each batch's plan against the exact reference solve; adds the
    batches found wrong to ``bad``."""
    lim = run.work["limits"]
    mismatch = illegal = 0
    for b, (t, pp, ps) in sorted(first.items()):
        starts, orders = batches[b]
        sim = reference.Sim(free, starts, orders,
                            **reference.sim_options(run.conf["solver"]))
        rt, rp, rs = sim.run()
        ill = int(reference.legality(free, np.concatenate(
            [starts[None], pp])).sum())
        m = min(t, rt)
        wrong = abs(t - rt) + int((pp[:m] != rp[:m]).any(axis=1).sum()
                                  + (ps[:m] != rs[:m]).any(axis=1).sum())
        if wrong or ill:
            bad.add(b)
            run.log(f"batch {b}: makespan {t} vs reference {rt}, "
                    f"{wrong} differing steps, {ill} illegal")
        mismatch += wrong
        illegal += ill
    run.log(f"judged {len(first)} batches against the exact reference")
    return [["illegal_moves", illegal, "<=", lim["illegal_moves"]],
            ["plan_mismatch", mismatch, "<=", lim["plan_mismatch"]],
            ["batches_judged", len(first), ">=", lim["batches_min"]]]
