#!/usr/bin/env python
"""Fleetsim: saturation-aware load generation + SLO verdicts (ISSUE 7).

The crossover harness (analysis/solver_crossover.py) proved that
simulated agents can close the task loop over the real wire; this
harness grows that into the production traffic rehearsal ROADMAP item 4
names: a load generator that multiplexes thousands of wire-faithful
agents in one process (runtime/simagent.py — pos1 region beacons,
trace-context propagation, done-retransmit), drives the sharded busd
pool + the centralized manager (+ solverd with ``--solver tpu``) at a
configurable load, and JUDGES the run against a declarative SLO spec
(obs/slo.py) evaluated from the fleet's own telemetry:

- fleet tasks/s + completion ratio: manager ``manager.tasks_dispatched``
  / ``manager.tasks_completed`` counter deltas over the measurement
  window (read from its ``mapd.metrics`` beacons — no harness-side
  instrumentation);
- phase-attributed latency: ``analysis/task_timeline.py`` percentiles
  over the run's lifecycle-event logs (JG_TRACE=1 is set by default),
  so a breached latency SLO names the phase that ate the budget;
- bus health: slow-consumer drops/evictions from the busd beacons, via
  the fleet aggregator rollup.

Modes:

- single run (default): one rung at ``--agents``/``--tick-ms``, verdict
  artifact written to ``--out`` (+ ``.md``), exit status = SLO gate
  (0 pass, 1 breach, 2 signal went dark) — the CI regression gate;
- ``--saturate N1,N2,...``: stepped-load search over agent counts (or
  ``--saturate-ticks T1,T2,...`` over tick periods at fixed agents):
  run rungs in order until an SLO breaches; the artifact records every
  rung's verdicts and the max sustainable tasks/s (the last passing
  rung), plus which SLO broke and the breaching phase.

Usage:
  python analysis/fleetsim.py --agents 1000 --shards 2 --out \\
      results/fleetsim_r09.json
  python analysis/fleetsim.py --agents 40 --side 24 --window 8 \\
      --settle 6 --spec ci_spec.json          # the scaled-down CI gate
  python analysis/fleetsim.py --saturate 250,500,1000,2000 --shards 3
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from p2p_distributed_tswap_tpu.obs import audit as _audit  # noqa: E402
from p2p_distributed_tswap_tpu.obs import capture as _capture  # noqa: E402
from p2p_distributed_tswap_tpu.obs import events as _events  # noqa: E402
from p2p_distributed_tswap_tpu.obs import flightrec as _flightrec  # noqa: E402
from p2p_distributed_tswap_tpu.obs import registry as _reg  # noqa: E402
from p2p_distributed_tswap_tpu.obs import trace as _trace  # noqa: E402
from p2p_distributed_tswap_tpu.obs import slo as _slo  # noqa: E402
from p2p_distributed_tswap_tpu.obs.beacon import METRICS_TOPIC  # noqa: E402
from p2p_distributed_tswap_tpu.obs.fleet_aggregator import (  # noqa: E402
    FleetAggregator, counter_total)
from p2p_distributed_tswap_tpu.obs.registry import hist_quantile  # noqa: E402
from p2p_distributed_tswap_tpu.runtime import buspool  # noqa: E402
from p2p_distributed_tswap_tpu.runtime import ha as _ha  # noqa: E402
from p2p_distributed_tswap_tpu.runtime import region as regionlib  # noqa: E402,E501
from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient  # noqa: E402,E501
from p2p_distributed_tswap_tpu.runtime.fleet import (  # noqa: E402
    BUILD_DIR, ensure_built, one_solverd_per_chip, wait_for_log)
from p2p_distributed_tswap_tpu.runtime.simagent import SimAgentPool  # noqa: E402,E501


class _PeerWindow:
    __slots__ = ("proc", "first", "last", "first_t", "last_t")

    def __init__(self, proc: str, metrics: dict, t: float):
        self.proc = proc
        self.first = self.last = metrics
        self.first_t = self.last_t = t


class MetricsWindow:
    """Ingest ``mapd.metrics`` beacons: feed the fleet aggregator (the
    rollup the SLO engine reads) and keep per-PEER first/last snapshots
    (keyed by peer_id — a busd pool's shards share the ``busd`` proc
    name) so window-scoped counter deltas are exact, not beacon-cadence
    approximations.  Each snapshot records its arrival time: rates
    divide by the FIRST→LAST BEACON span, not the harness's window
    wall clock (beacons land up to an interval late on either edge)."""

    def __init__(self, port: int, audit: bool = False, ha: bool = False):
        self.bus = BusClient(port=port, peer_id="fleetsim-watch")
        self.bus.subscribe(METRICS_TOPIC)
        if audit and _audit.enabled():
            # replay mode joins the audit plane too: final-watermark
            # ledger/view digests are the determinism proof (ISSUE 11)
            self.bus.subscribe(_audit.AUDIT_TOPIC, raw=True)
        if ha:
            # HA replays (ISSUE 15) watch mapd.ha too: the aggregator
            # records ha_takeover announcements — the digest-equal
            # takeover proof the failover judges read
            self.bus.subscribe(_ha.HA_TOPIC, raw=True)
        self.agg = FleetAggregator()
        self._peers = {}  # peer_id -> _PeerWindow

    def pump(self, budget_s: float) -> None:
        end = time.monotonic() + budget_s
        while True:
            remaining = end - time.monotonic()
            if remaining <= 0:
                return
            f = self.bus.recv(timeout=min(0.2, remaining))
            if not f or f.get("op") != "msg":
                continue
            d = f.get("data") or {}
            if d.get("type") != "metrics_beacon":
                # audit beacons (and the replay driver's own beacons)
                # route into the aggregator but never into the per-peer
                # metrics windows — their payloads carry no counters
                self.agg.ingest(d)
                continue
            if not self.agg.ingest(d):
                continue
            proc = d.get("proc", "?")
            key = str(d.get("peer_id") or proc)
            m = d.get("metrics") or {}
            now = time.monotonic()
            st = self._peers.get(key)
            if st is None:
                self._peers[key] = _PeerWindow(proc, m, now)
            else:
                st.last = m
                st.last_t = now

    def reset_window(self) -> None:
        """Measurement window starts fresh (the aggregator keeps its
        history: its delta rates want consecutive beacons)."""
        self._peers.clear()

    def seen(self, proc: str) -> bool:
        return any(st.proc == proc for st in self._peers.values())

    def delta(self, proc: str, counter: str) -> float:
        """Window delta of a counter summed over every peer of ``proc``,
        clamped at zero per peer (a restart inside the window resets
        cumulative counters)."""
        total = 0.0
        for st in self._peers.values():
            if st.proc != proc or st.last is st.first:
                continue
            total += max(0.0, counter_total(st.last, counter)
                         - counter_total(st.first, counter))
        return total

    def span_s(self, proc: str) -> float:
        """Longest first→last beacon span among ``proc``'s peers — the
        honest denominator for the window delta rates."""
        return max((st.last_t - st.first_t for st in self._peers.values()
                    if st.proc == proc), default=0.0)

    def close(self) -> None:
        self.bus.close()


class WorldToggler:
    """Dynamic-obstacle injection (ISSUE 9): every ``every`` seconds,
    reopen the previous wall and ask the manager to close a fresh random
    vertical wall of ``cells`` cells (world_update_request on "mapd").
    The MANAGER validates and applies — cells under agents, goals or
    task endpoints come back rejected, which is the correct behavior,
    not a harness failure; the pool's world counters record what
    actually landed."""

    def __init__(self, sim: SimAgentPool, side: int, cells: int,
                 every: float, seed: int):
        import random

        self.sim = sim
        self.side = side
        self.cells = cells
        self.every = every
        self.rng = random.Random(seed)
        self.prev = []
        self.next_at = time.monotonic() + every
        self.sent = 0

    def maybe(self) -> None:
        if not self.cells or time.monotonic() < self.next_at:
            return
        self.next_at = time.monotonic() + self.every
        toggles = [[x, y, 0] for x, y in self.prev]  # reopen the old wall
        x0 = self.rng.randrange(2, max(3, self.side - 2))
        y0 = self.rng.randrange(0, max(1, self.side - self.cells))
        wall = [(x0, y0 + i) for i in range(self.cells)]
        toggles += [[x, y, 1] for x, y in wall]
        self.prev = wall
        self.sim.bus.publish("mapd", {"type": "world_update_request",
                                      "toggles": toggles})
        self.sent += 1

    def reopen_all(self) -> None:
        """End-of-run cleanup: reopen the last wall so the drain phase
        (in-flight tasks finishing) faces the static world again."""
        if self.prev:
            self.sim.bus.publish(
                "mapd", {"type": "world_update_request",
                         "toggles": [[x, y, 0] for x, y in self.prev]})
            self.prev = []


def _timeline_summary(trace_dir: Path) -> dict:
    from analysis import task_timeline

    summary = task_timeline.summarize(trace_dir)
    summary.pop("tasks", None)  # per-task records stay out of artifacts
    return summary


def _federation_counters(watch, mgr_proc: str) -> dict:
    """Window deltas of the handoff-protocol counters, summed across
    every region manager — the one evidence dict both the load rungs
    and the chaos judge read (keep them from diverging)."""
    return {
        "handoffs_sent": int(watch.delta(
            mgr_proc, "manager.handoffs_sent")),
        "handoffs_acked": int(watch.delta(
            mgr_proc, "manager.handoffs_acked")),
        "handoffs_received": int(watch.delta(
            mgr_proc, "manager.handoffs_received")),
        "handoffs_dup_dropped": int(watch.delta(
            mgr_proc, "manager.handoffs_dup_dropped")),
        "handoff_retransmits": int(watch.delta(
            mgr_proc, "manager.handoff_retransmits")),
        "handoff_outbox_overflow": int(watch.delta(
            mgr_proc, "manager.handoff_outbox_overflow")),
        "conflict_releases": int(watch.delta(
            mgr_proc, "manager.fed_conflict_releases")),
    }


def shape_rate(shape: str, t_s: float, base: float, peak: float,
               period_s: float) -> float:
    """Open-loop injection rate (tasks/s) at elapsed time ``t_s`` for a
    traffic shape (ISSUE 16) — the rehearsal generators healthd's
    forecast is validated against:

    - ``ramp``  — diurnal climb: linear base→peak across the period,
      held at peak after (the smooth monotone trend a slope forecaster
      must catch BEFORE the breach);
    - ``flash`` — flash crowd: base load with a peak burst through the
      last 20% of each period (a step, which must NOT forecast — it
      confirms the fast way, via the burn window);
    - ``storm`` — tenant arrival storm: a 4-step staircase base→peak
      per period (each arriving tenant adds a load quantum).

    ``none`` (or an unknown shape) is constant ``base`` — the legacy
    open-loop wire, byte-identical.
    """
    if shape in (None, "", "none") or period_s <= 0:
        return base
    if shape == "ramp":
        frac = min(1.0, max(0.0, t_s / period_s))
        return base + (peak - base) * frac
    phase = (t_s % period_s) / period_s
    if shape == "flash":
        return peak if phase >= 0.8 else base
    if shape == "storm":
        step = min(3, int(phase * 4))
        return base + (peak - base) * step / 3.0
    return base


def _fed_spec(args):
    """``(cols, rows, total)`` from the rung's --regions spec (None/1 =
    the single-pair fleet)."""
    cols, rows = regionlib.fed_parse_spec(getattr(args, "regions", None))
    return cols, rows, cols * rows


def run_rung(args, agents: int, tick_ms: int, spec) -> dict:
    """One measured load rung: fresh fleet, settle, window, verdicts.

    With ``--regions CxR`` (ISSUE 14) the fleet is FEDERATED: one
    (manager [, solverd]) pair per region on the shared bus pool, each
    manager owning its rectangle and sampling its own pickups, one
    world-spanning sim pool driven through all of them; the window
    signals sum across managers and the rung grows a ``federation``
    section (per-region tasks/s + handoff counters)."""
    import shutil

    ensure_built()
    fed_cols, fed_rows, fed_total = _fed_spec(args)
    home_port = buspool.free_port()
    log_dir = Path(args.log_dir) / (f"a{agents}_t{tick_ms}_s{args.shards}"
                                    + (f"_r{fed_cols}x{fed_rows}"
                                       if fed_total > 1 else ""))
    # a fresh rung directory every time: event logs append per-pid and
    # task_timeline merges every *.events.jsonl it finds, so a stale
    # previous run at the same config (the CI gate's fixed --log-dir)
    # would dilute — or fail — this run's phase signals
    if log_dir.exists():
        shutil.rmtree(log_dir)
    log_dir.mkdir(parents=True, exist_ok=True)
    trace_dir = log_dir / "trace"
    saved_env = dict(os.environ)
    procs, logs = [], []

    def spawn(name, cmd, stdin=None, env=None):
        log = open(log_dir / f"{name}.log", "w")
        logs.append(log)
        p = subprocess.Popen(cmd, stdin=stdin, stdout=log,
                             stderr=subprocess.STDOUT,
                             env=dict(os.environ, **(env or {})))
        procs.append(p)
        return p

    pool = watch = sim = None
    # fresh harness-process registry per rung: in a saturation ladder the
    # pool's claim-wire histogram must not carry the previous rung's
    # samples into this rung's p99
    _reg.get_registry().clear()
    try:
        pool = buspool.BusPool(
            BUILD_DIR / "mapd_bus", num_shards=args.shards,
            home_port=home_port, spawn=spawn)
        time.sleep(0.4)
        # the harness process hosts the sim pool: it needs the same
        # fleet environment the children get (shard map, trace sinks)
        os.environ.update(pool.env())
        if not args.no_trace:
            os.environ["JG_TRACE"] = "1"
            os.environ["JG_TRACE_DIR"] = str(trace_dir)
            os.environ.setdefault("JG_TRACE_SAMPLE", "1.0")
        os.environ.setdefault("JG_FLIGHT_DIR", str(log_dir))
        # re-arm the harness-process sinks under the rung environment:
        # the span tracer caches JG_TRACE at configure time, and the sim
        # pool's lifecycle events only reach disk with it armed
        _trace.configure(proc="simfleet")
        _events.configure("simfleet")
        if args.solver == "tpu":
            one_solverd_per_chip(fed_total, args.solverd_cpu)
            for rid in range(fed_total):
                tag = f"_r{rid}" if fed_total > 1 else ""
                sd_cmd = [sys.executable, "-m",
                          "p2p_distributed_tswap_tpu.runtime.solverd",
                          "--port", str(home_port), "--map",
                          args.map_file, "--warm", str(agents),
                          *(["--cpu"] if args.solverd_cpu else []),
                          *regionlib.fed_cli_args(rid, fed_cols,
                                                  fed_rows, "solverd")]
                sd_proc = spawn(f"solverd{tag}", sd_cmd)
                if not wait_for_log(log_dir / f"solverd{tag}.log",
                                    "solverd up", 900, proc=sd_proc):
                    raise RuntimeError(f"solverd{tag} never became ready")
        mgrs = []
        for rid in range(fed_total):
            tag = f"_r{rid}" if fed_total > 1 else ""
            mgr_cmd = [
                str(BUILD_DIR / "mapd_manager_centralized"),
                "--port", str(home_port), "--map", args.map_file,
                "--solver", "cpu" if args.solver == "native" else "tpu",
                "--planning-interval-ms", str(tick_ms),
                "--max-tracked-agents", str(agents + 16),
                # seed audit (ISSUE 11): the manager's task sampling is
                # the last stochastic path fleetsim touches — thread the
                # one harness seed through it so a rung is re-runnable
                # (per-region offset keeps the samplers independent)
                "--seed", str(args.seed + rid),
                *regionlib.fed_cli_args(rid, fed_cols, fed_rows,
                                        "manager")]
            mgrs.append(spawn(f"manager{tag}", mgr_cmd,
                              stdin=subprocess.PIPE))
        mgr = mgrs[0]
        time.sleep(0.5)
        sim = SimAgentPool(agents, args.side, port=home_port,
                           seed=args.seed, heartbeat_s=args.heartbeat_s)
        recorder = None
        if getattr(args, "capture", None):
            # traffic capture (ISSUE 11): record every dispatched task
            # and accepted world update as replayable traffic, anchored
            # at pool creation so the ramp is part of the window
            recorder = _capture.CaptureRecorder({
                "agents": agents, "side": args.side, "seed": args.seed,
                "shards": args.shards, "solver": args.solver,
                "tick_ms": tick_ms, "heartbeat_s": args.heartbeat_s,
                "manager_seed": args.seed})
            sim.capture = recorder
            # harness-side config into the flight ring: the post-mortem
            # assembly path (blackbox --capture) merges it with the
            # pool's own capture.meta
            _events.emit("capture.meta", shards=args.shards,
                         solver=args.solver, tick_ms=tick_ms,
                         manager_seed=args.seed)
        watch = MetricsWindow(home_port)
        sim.heartbeat_all()
        sim.pump(1.5)

        def inject(k):
            # federated fleets split the injection across region
            # managers (each samples pickups in its own rectangle)
            share = -(-k // fed_total)
            left = k
            for m in mgrs:
                n = min(share, left)
                if n <= 0:
                    break
                m.stdin.write(f"tasks {n}\n".encode())
                m.stdin.flush()
                left -= n

        open_loop = args.mode == "open"
        inject_every = 1.0
        per_inject = max(1, int(round(args.rate * inject_every)))
        # traffic shapes (ISSUE 16): the open-loop rate becomes a
        # function of elapsed time; `none` keeps the legacy constant
        # wire exactly (per_inject path untouched)
        shape = getattr(args, "shape", "none") or "none"
        shape_peak = getattr(args, "shape_peak", None)
        if shape_peak is None:
            shape_peak = 4.0 * args.rate
        shape_period = getattr(args, "shape_period_s", None)
        if shape_period is None:
            shape_period = args.settle + args.window
        shape_t0 = time.monotonic()
        if not open_loop:
            # ramped closed-loop fill (manager refills on every done):
            # the fleet's standing load goes out in chunks, so
            # dispatch->claim measures the steady wire rather than one
            # thundering-herd burst the pool drains for seconds
            ramp_s = min(args.ramp_s, args.settle * 0.5)
            steps = max(1, int(ramp_s / 0.5))
            chunk = -(-agents // steps)
            sent = 0
            while sent < agents:
                inject(min(chunk, agents - sent))
                sent += chunk
                sim.pump(0.45)
                watch.pump(0.05)

        toggler = WorldToggler(sim, args.side, args.world_toggle_cells,
                               args.world_toggle_every, args.seed + 77)

        def drive(seconds: float):
            nonlocal next_inject
            end = time.monotonic() + seconds
            while time.monotonic() < end:
                if open_loop and time.monotonic() >= next_inject:
                    next_inject = time.monotonic() + inject_every
                    if shape != "none":
                        rate = shape_rate(shape,
                                          time.monotonic() - shape_t0,
                                          args.rate, shape_peak,
                                          shape_period)
                        k = int(round(rate * inject_every))
                        if k > 0:
                            inject(k)
                    else:
                        inject(per_inject)
                toggler.maybe()
                sim.pump(0.3)
                watch.pump(0.05)

        next_inject = shape_t0 = time.monotonic()
        drive(args.settle)
        # measurement window starts fresh: counters re-baseline, the sim
        # pool's own done count snapshots
        watch.reset_window()
        sim_done0 = sim.done_count
        t0 = time.monotonic()
        drive(args.window)
        wall = time.monotonic() - t0
        if toggler.sent:
            # reopen the final wall so the post-window drain (in-flight
            # tasks completing, done-acks landing) faces the static map
            toggler.reopen_all()
            sim.pump(1.0)
        watch.pump(2.5)  # one more beacon interval: final counters land

        rollup = watch.agg.rollup()
        signals = _slo.signals_from_rollup(rollup)
        # window-exact overrides: beacon-cadence delta rates are the
        # live view; the SLO verdict wants the measured window.  The
        # rate denominator is the manager's own first->last beacon span
        # (its counters move with its beacons, not with our wall clock).
        mgr_proc = "manager_centralized"
        d_disp = watch.delta(mgr_proc, "manager.tasks_dispatched")
        d_done = watch.delta(mgr_proc, "manager.tasks_completed")
        span = watch.span_s(mgr_proc)
        if watch.seen(mgr_proc) and span > 0:
            signals["fleet.tasks_per_s"] = round(d_done / span, 3)
            if d_disp > 0:
                signals["fleet.completion_ratio"] = round(
                    min(1.0, d_done / d_disp), 4)
            elif d_done > 0:
                # window with completions but no fresh dispatches (e.g.
                # drain phase): everything that could complete did
                signals["fleet.completion_ratio"] = 1.0
        else:
            # <2 manager beacons in the window = DARK telemetry: drop
            # the rollup-derived values too (they span the settle
            # phase) so the SLO reads unknown (exit 2) — never a stale
            # pre-window rate passing as measurement, never a
            # fabricated 0.0 breach
            signals.pop("fleet.tasks_per_s", None)
            signals.pop("fleet.completion_ratio", None)
        if watch.seen("busd") and watch.span_s("busd") > 0:
            # bus-shedding SLOs judge the MEASURED WINDOW ("zero
            # evictions at rated load"), not the warm-up thundering
            # herd the cumulative busd counters include.  With <2
            # beacons per shard the cumulative rollup value stands —
            # conservative (includes warm-up), never a fabricated 0.
            signals["bus.slow_consumer_evictions"] = int(
                watch.delta("busd", "bus.slow_consumer_evictions"))
            signals["bus.slow_consumer_drops"] = int(
                watch.delta("busd", "bus.slow_consumer_drops"))
        # always-on claim-wire percentile from the pool's own registry
        # (hop_latency_ms{edge="task.claim"}) — works without JG_TRACE
        snap = _reg.snapshot()
        claim = (snap.get("hists") or {}).get(
            'hop_latency_ms{edge="task.claim"}')
        if claim and claim.get("count"):
            signals["sim.claim_wire_p99_ms"] = round(
                hist_quantile(claim, 0.99), 3)
            signals["sim.claim_wire_p50_ms"] = round(
                hist_quantile(claim, 0.5), 3)
        if open_loop and shape != "none":
            # shape evidence rides the signals (ISSUE 16): the health
            # artifact (and item 1's rehearsals) record exactly which
            # traffic curve the verdict was judged under
            signals["shape.kind"] = shape
            signals["shape.base_rate"] = args.rate
            signals["shape.peak_rate"] = round(shape_peak, 3)
            signals["shape.period_s"] = round(shape_period, 1)
        if toggler.sent:
            # dynamic-world evidence rides the signals so a spec can
            # demand toggles actually landed (unknown = exit 2 otherwise)
            signals["world.requests"] = toggler.sent
            signals["world.updates_seen"] = sim.world_updates
            signals["world.toggles_accepted"] = sim.world_accepted
        federation = None
        if fed_total > 1:
            # federation evidence (ISSUE 14): window handoff counters
            # summed across region managers + the aggregator's
            # per-region view — the signals a spec can gate on
            federation = {
                "regions": f"{fed_cols}x{fed_rows}",
                "region_count": fed_total,
                **_federation_counters(watch, mgr_proc),
                "per_region": (rollup.get("federation") or {}).get(
                    "per_region"),
            }
            signals["fed.handoffs_sent"] = federation["handoffs_sent"]
            signals["fed.handoffs_acked"] = federation["handoffs_acked"]
            signals["fed.handoffs_dup_dropped"] = \
                federation["handoffs_dup_dropped"]
        timeline = None
        if not args.no_trace and trace_dir.exists():
            timeline = _timeline_summary(trace_dir)
            signals.update(_slo.signals_from_timeline(timeline))
        result = _slo.evaluate(spec, signals)
        rung = {
            "agents": agents,
            "tick_ms": tick_ms,
            "shards": args.shards,
            "regions": f"{fed_cols}x{fed_rows}" if fed_total > 1 else None,
            "mode": args.mode,
            "solver": args.solver,
            "map": f"{args.side}x{args.side} empty",
            "window_s": round(wall, 1),
            "settle_s": args.settle,
            "seed": args.seed,
            "window_tasks_dispatched": int(d_disp),
            "window_tasks_completed": int(d_done),
            "sim": {**sim.stats(),
                    "done_in_window": sim.done_count - sim_done0},
            "signals": signals,
            "slo": result,
        }
        if open_loop and shape != "none":
            rung["shape"] = {"kind": shape, "base_rate": args.rate,
                             "peak_rate": round(shape_peak, 3),
                             "period_s": round(shape_period, 1)}
        if federation is not None:
            rung["federation"] = federation
        if toggler.sent:
            rung["world"] = {
                "toggle_cells": args.world_toggle_cells,
                "toggle_every_s": args.world_toggle_every,
                "requests": toggler.sent,
                "updates_seen": sim.world_updates,
                "toggles_accepted": sim.world_accepted,
                "toggles_rejected": sim.world_rejected,
            }
        if timeline is not None:
            rung["timeline"] = timeline
        if recorder is not None:
            phase_p95 = {
                ph: pcts.get("p95")
                for ph, pcts in ((timeline or {}).get("fleet_phases_ms")
                                 or {}).items()
                if isinstance(pcts, dict)
                and pcts.get("p95") is not None}
            baseline = {
                "window_s": round(wall, 1),
                "tasks_per_s": signals.get("fleet.tasks_per_s"),
                "completion_ratio": signals.get("fleet.completion_ratio"),
                "claim_wire_p99_ms": signals.get("sim.claim_wire_p99_ms"),
                "phase_p95_ms": phase_p95 or None,
            }
            try:
                path = _capture.save(args.capture,
                                     recorder.finalize(baseline=baseline))
            except _capture.CaptureError as e:
                # an unreplayable window (e.g. no task ever dispatched)
                # must not discard the rung's own verdicts/signals — the
                # window is still diagnosable, just not re-drivable
                rung["capture_error"] = str(e)
                print(f"fleetsim: capture SKIPPED: {e}", flush=True)
            else:
                rung["capture"] = str(path)
                print(f"fleetsim: capture1 written to {path} "
                      f"({len(recorder.tasks)} task(s), "
                      f"{len(recorder.world)} world event(s))",
                      flush=True)
        return rung
    finally:
        for obj in (sim, watch):
            if obj is not None:
                obj.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=3)
            except subprocess.TimeoutExpired:
                p.kill()
        if pool is not None:
            pool.close()
        for log in logs:
            log.close()
        # the harness's own flight ring holds the capture evidence
        # (capture.meta / task.spec / world.update, ISSUE 11) — dump it
        # into the run's log dir NOW, while we still know which run this
        # was: the atexit dump fires after the env restore below and
        # would land in the default dir, stranding the post-mortem
        # `blackbox.py --capture` path for in-process windows
        _rec = _flightrec.get_recorder()
        _flightrec.dump(str(log_dir / f"{_rec.proc}-{_rec.pid}"
                                      ".flight.jsonl"),
                        reason="rung_teardown")
        os.environ.clear()
        os.environ.update(saved_env)
        # re-bind the sinks to the restored environment
        _trace.configure(proc="simfleet")
        _events.configure("simfleet")


class ReplayCtx:
    """The live handles a chaos fault script pokes at (ISSUE 11,
    scripts/chaos_gate.py): the busd pool (kill_shard / SIGSTOP a
    member), the manager and solverd processes (signals), the sim pool,
    and a solverd respawner for kill-and-recover faults.  ``notes``
    accumulates a human-readable fault log that rides the replay
    artifact."""

    def __init__(self, pool, mgr, sim, solverd, start_solverd,
                 managers=None, solverds=None, standbys=None):
        self.pool = pool
        self.manager = mgr
        # warm standbys (ISSUE 15), index = region id; empty without HA
        self.standbys = list(standbys) if standbys else []
        # federated replays (ISSUE 14): every region manager/solverd,
        # index = region id — the handoff-kill fault targets
        # managers[1]; a fault combining regions with a solverd
        # kill/restart must target (and respawn) the RIGHT region's
        # daemon or one plan wire goes dark while another doubles up
        self.managers = list(managers) if managers else [mgr]
        self.solverds = (list(solverds) if solverds
                         else ([solverd] if solverd is not None else []))
        self.sim = sim
        self.solverd = solverd
        self._start_solverd = start_solverd
        self._solverd_generation = 0
        self.notes: list = []

    def note(self, text: str) -> None:
        self.notes.append(text)
        print(f"chaos: {text}", flush=True)

    def restart_solverd(self, wait: bool = False, rid: int = 0):
        """Respawn region ``rid``'s solverd on ITS plan-wire topic
        (default non-blocking: a chaos recovery must not stall the
        replay loop for the whole JAX warmup — the fleet's own resync
        machinery picks the daemon up when it's ready)."""
        self._solverd_generation += 1
        tag = ((f"_r{rid}" if len(self.solverds) > 1 else "")
               + f"_g{self._solverd_generation}")
        p = self._start_solverd(tag, wait=wait, rid=rid)
        while len(self.solverds) <= rid:
            self.solverds.append(None)
        self.solverds[rid] = p
        if rid == 0:
            self.solverd = p
        return p


def _final_digests(joiner) -> dict:
    """The determinism proof's raw material: each role's NEWEST audit
    digests at the drained watermark — the manager's ledger / in-flight
    view / lane shadow, the agent pool's held view, solverd's mirror.
    Ledger and view must be equal across two replays of one capture
    (both sides fully drained); lane digests (positions) are recorded
    for diagnosis only — assignment interleaving is the live planner's."""
    out = {}
    for name, st in joiner._peers.items():
        sections = []
        if st.proc.startswith("manager"):
            sections = [(_audit.SEC_LEDGER, "ledger"),
                        (_audit.SEC_VIEW, "view"),
                        (_audit.SEC_SHADOW, "lanes")]
        elif st.proc.startswith("solverd"):
            sections = [(_audit.SEC_MIRROR, "mirror")]
        elif st.proc == "simagent_pool":
            sections = [(_audit.SEC_VIEW, "view_agents")]
        for sec, key in sections:
            e = st.latest.get(sec)
            if e is not None:
                out[key] = {"peer": name,
                            "digest": _audit.digest_hex(e.digest),
                            "count": e.count, "seq": e.seq,
                            "epoch": e.epoch}
    return out


def run_replay(capture: dict, log_dir, solver=None, shards=None,
               no_trace: bool = False, chaos=None, drain_s=None,
               label: str = "replay", regions=None,
               ha: bool = False, solverd_cpu: bool = False) -> dict:
    """Re-drive a captured window open-loop as a DETERMINISTIC load
    (ISSUE 11): a fresh fleet (seeded from the capture), the captured
    tasks injected via the manager's ``taskat`` command at their
    original arrival offsets with their original ids and endpoints, the
    captured world toggles re-requested at their offsets — then drain
    until every captured task completed (or timeout).  ``chaos``, when
    given, is polled with ``(ctx, t_rel_s)`` throughout and may kill /
    stop / restart fleet members (scripts/chaos_gate.py).

    ``regions`` (ISSUE 14): a "CxR" spec replays the capture through a
    FEDERATED fleet — per-region (manager [, solverd]) pairs, each
    captured task injected into the manager owning its pickup cell —
    so the chaos matrix can fault a region manager mid-handoff.

    Returns the replay record: outcome ledger (completed ids, missing,
    duplicates), final-watermark audit digests, the auditor's confirmed
    divergences, and fidelity drift vs the capture baseline."""
    import shutil

    capture = _capture.validate(capture)
    fleet = capture["fleet"]
    agents, side = fleet["agents"], fleet["side"]
    solver = solver or fleet.get("solver") or "native"
    shards = int(shards or fleet.get("shards") or 1)
    tick_ms = int(fleet.get("tick_ms") or 250)
    seed = int(fleet.get("seed") or 1)
    mseed = fleet.get("manager_seed")
    mseed = seed if mseed is None else int(mseed)
    heartbeat_s = float(fleet.get("heartbeat_s") or 2.0)
    fed_cols, fed_rows = regionlib.fed_parse_spec(regions)
    fed_total = fed_cols * fed_rows
    if solver == "tpu":
        one_solverd_per_chip(fed_total, solverd_cpu)

    ensure_built()
    map_file = f"/tmp/fleetsim_replay_{side}.map.txt"
    Path(map_file).write_text("\n".join(["." * side] * side) + "\n")
    home_port = buspool.free_port()
    log_dir = Path(log_dir) / label
    if log_dir.exists():
        shutil.rmtree(log_dir)
    log_dir.mkdir(parents=True, exist_ok=True)
    trace_dir = log_dir / "trace"
    saved_env = dict(os.environ)
    procs, logs = [], []

    def spawn(name, cmd, stdin=None, env=None):
        log = open(log_dir / f"{name}.log", "w")
        logs.append(log)
        p = subprocess.Popen(cmd, stdin=stdin, stdout=log,
                             stderr=subprocess.STDOUT,
                             env=dict(os.environ, **(env or {})))
        procs.append(p)
        return p

    pool = watch = sim = None
    _reg.get_registry().clear()
    try:
        pool = buspool.BusPool(
            BUILD_DIR / "mapd_bus", num_shards=shards,
            home_port=home_port, spawn=spawn)
        time.sleep(0.4)
        os.environ.update(pool.env())
        if not no_trace:
            os.environ["JG_TRACE"] = "1"
            os.environ["JG_TRACE_DIR"] = str(trace_dir)
            os.environ.setdefault("JG_TRACE_SAMPLE", "1.0")
        os.environ.setdefault("JG_FLIGHT_DIR", str(log_dir))
        # fast audit cadence: the final-watermark digests are the
        # determinism proof, and the chaos judge needs silent-peer
        # detection well inside the drain budget
        os.environ.setdefault("JG_AUDIT_INTERVAL_MS", "400")
        os.environ.setdefault("JG_AUDIT_INTERVAL_S", "0.4")
        if capture.get("world"):
            # replayed toggles must reach solverd from tick one
            os.environ.setdefault("JG_DYNAMIC_WORLD", "1")
        if ha:
            # control-plane HA (ISSUE 15): every region manager ships
            # its ledger1 stream and gets a warm standby below
            os.environ["JG_HA"] = "1"
        if chaos is not None and getattr(chaos, "needs_shm", False):
            # zero-copy lanes (ISSUE 18): the lane faults replay with
            # the rings armed for every client spawned below (and the
            # in-process sim pool); ring files live with the run's logs
            os.environ["JG_BUS_SHM"] = "1"
            os.environ.setdefault("JG_BUS_SHM_DIR",
                                  str(log_dir / "shm_lanes"))
        _trace.configure(proc="simfleet")
        _events.configure("simfleet")

        def start_solverd(tag: str = "", wait: bool = True, rid: int = 0):
            name = f"solverd{tag}"
            cmd = [sys.executable, "-m",
                   "p2p_distributed_tswap_tpu.runtime.solverd",
                   "--port", str(home_port), "--map", map_file,
                   "--warm", str(agents),
                   *(["--cpu"] if solverd_cpu else []),
                   *regionlib.fed_cli_args(rid, fed_cols, fed_rows,
                                           "solverd")]
            p = spawn(name, cmd)
            if wait and not wait_for_log(log_dir / f"{name}.log",
                                         "solverd up", 900, proc=p):
                raise RuntimeError(f"{name} never became ready")
            return p

        sds = []
        if solver == "tpu":
            for rid in range(fed_total):
                sds.append(start_solverd(
                    f"_r{rid}" if fed_total > 1 else "", rid=rid))
        sd = sds[0] if sds else None
        mgrs, stbys = [], []
        for rid in range(fed_total):
            tag = f"_r{rid}" if fed_total > 1 else ""
            cmd = [str(BUILD_DIR / "mapd_manager_centralized"),
                   "--port", str(home_port), "--map", map_file,
                   "--solver", "cpu" if solver == "native" else "tpu",
                   "--planning-interval-ms", str(tick_ms),
                   "--max-tracked-agents", str(agents + 16),
                   "--seed", str(mseed + rid),
                   # open-loop: completions must NOT mint fresh rng
                   # tasks — the load is exactly the captured taskat
                   # stream
                   "--open-loop",
                   *regionlib.fed_cli_args(rid, fed_cols, fed_rows,
                                           "manager")]
            if ha:
                cmd += ["--ha", "1"]
            mgrs.append(spawn(f"manager{tag}", cmd,
                              stdin=subprocess.PIPE))
            if ha:
                # the warm standby tails the active's ledger1 stream;
                # taskat lines sent to it while the active lives are
                # deferred and drained at promotion
                stbys.append(spawn(f"standby{tag}",
                                   cmd + ["--standby"],
                                   stdin=subprocess.PIPE))
        mgr = mgrs[0]
        time.sleep(0.5)
        sim = SimAgentPool(agents, side, port=home_port, seed=seed,
                           heartbeat_s=heartbeat_s)
        watch = MetricsWindow(home_port, audit=True, ha=ha)
        sim.heartbeat_all()
        sim.pump(1.5)
        watch.pump(0.5)

        ctx = ReplayCtx(pool, mgr, sim, sd, start_solverd, managers=mgrs,
                        solverds=sds, standbys=stbys)
        events = _capture.schedule(capture)
        expected = set(_capture.task_ids(capture))
        baseline = capture.get("baseline") or {}
        orig_tps = baseline.get("tasks_per_s")
        injected = world_injected = 0
        last_beacon = [0.0]
        last_eval = [0.0]
        t0 = time.monotonic()
        t0_wall_ms = time.time_ns() // 1_000_000

        def replay_beacon(final: bool = False, extra: dict = None):
            """Progress on the metrics plane: fleet_top's REPLAY line
            and the aggregator's replay section render this."""
            elapsed = max(time.monotonic() - t0, 1e-9)
            done = len(sim.done_ids & expected)
            payload = {"type": "replay_beacon",
                       "peer_id": "replay-driver",
                       "proc": "replay",
                       "capture_source": capture.get("source"),
                       "t_s": round(elapsed, 1),
                       "injected": injected,
                       "total": len(expected),
                       "world_injected": world_injected,
                       "done": done,
                       "done_dups": sim.done_dups,
                       "tasks_per_s": round(done / elapsed, 3),
                       "orig_tasks_per_s": orig_tps,
                       "final": final}
            payload.update(extra or {})
            sim.bus.publish(METRICS_TOPIC, payload)
            return payload

        def tick(slice_s: float):
            now = time.monotonic()
            if chaos is not None:
                chaos.poll(ctx, now - t0)
            sim.pump(slice_s)
            watch.pump(0.02)
            if now - last_eval[0] >= 0.5:
                last_eval[0] = now
                watch.agg.audit.evaluate()
            if now - last_beacon[0] >= 2.0:
                last_beacon[0] = now
                replay_beacon()

        for t_ms, kind, payload in events:
            target = t0 + t_ms / 1000.0
            while True:
                remaining = target - time.monotonic()
                if remaining <= 0:
                    break
                tick(min(0.1, remaining))
            if kind == "task":
                px, py = payload["pickup"]
                dx, dy = payload["delivery"]
                # federated replays route each task to the manager that
                # OWNS its pickup cell (the ownership canon); a manager
                # a chaos fault already killed loses its stream UNLESS
                # an HA standby stands in — the standby defers taskat
                # lines and drains them at promotion (ISSUE 15), so a
                # failover replay loses nothing
                rid0 = 0
                if fed_total > 1:
                    rid0 = regionlib.fed_region_of(
                        int(px), int(py), fed_cols, fed_rows, side, side)
                tgt = mgrs[rid0]
                if tgt.poll() is not None and rid0 < len(stbys) \
                        and stbys[rid0].poll() is None:
                    tgt = stbys[rid0]
                try:
                    tgt.stdin.write(
                        f"taskat {px} {py} {dx} {dy} "
                        f"{payload['id']}\n".encode())
                    tgt.stdin.flush()
                    injected += 1
                except (BrokenPipeError, OSError):
                    ctx.note(f"task {payload['id']} lost: its region "
                             "manager is down")
            else:
                sim.bus.publish("mapd", {"type": "world_update_request",
                                         "toggles": payload["toggles"]})
                world_injected += 1
        inject_wall_s = time.monotonic() - t0
        dur_s = capture["duration_ms"] / 1000.0
        budget = (drain_s if drain_s is not None else max(30.0, dur_s))
        budget += getattr(chaos, "extra_drain_s", 0.0) or 0.0
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline \
                and not expected <= sim.done_ids:
            tick(0.25)
        drained = expected <= sim.done_ids
        # the final watermark: stop injecting, let every role beacon its
        # drained digests (>= 3 audit intervals), judge one last time
        end_pump = time.monotonic() + 2.5
        while time.monotonic() < end_pump:
            tick(0.2)
        watch.pump(1.0)
        watch.agg.audit.evaluate()

        completed = sorted(sim.done_ids & expected)
        missing = sorted(expected - sim.done_ids)
        extra_done = sorted(sim.done_ids - expected)
        # ledger-level completion count: the manager's dedup-guarded
        # counter — each id counts at most once, so > expected means the
        # system of record double-counted (a real duplication), while
        # pool-side done_dups also catches the benign positional-done /
        # goal-exchange race the reference architecture carries
        mgr_proc = "manager_centralized"
        mgr_completed = int(watch.delta(mgr_proc,
                                        "manager.tasks_completed"))
        mgr_dispatched = int(watch.delta(mgr_proc,
                                         "manager.tasks_dispatched"))
        federation = None
        if fed_total > 1:
            # the chaos judge's handoff evidence: summed across every
            # region manager's beacons over the whole replay window
            federation = {
                "regions": f"{fed_cols}x{fed_rows}",
                **_federation_counters(watch, mgr_proc),
            }
        ha_section = None
        if ha:
            # the failover judge's evidence (ISSUE 15): every observed
            # takeover announcement with its digest-equality verdict
            # and its latency relative to the replay clock (the fault
            # records when it killed the active on the same clock)
            takeovers = []
            for rec in watch.agg.ha_takeovers:
                # the ONE digest-equality rule (runtime/ha.py): None =
                # cold start (nothing shipped to compare) — the chaos
                # judges treat that as failing the proof, correctly
                eq = _ha.takeover_digests_equal(rec)
                takeovers.append({
                    "peer": rec.get("peer_id"),
                    "ns": rec.get("ns"),
                    "why": rec.get("why"),
                    "repl_seq": rec.get("repl_seq"),
                    "pending": rec.get("pending"),
                    "inflight": rec.get("inflight"),
                    "ledger_digest": rec.get("ledger_digest"),
                    "active_ledger_digest":
                        rec.get("active_ledger_digest"),
                    "view_digest": rec.get("view_digest"),
                    "active_view_digest": rec.get("active_view_digest"),
                    "digests_equal": eq,
                    "t_rel_s": round(
                        (rec["seen_ms"] - t0_wall_ms) / 1000.0, 2),
                })
            ha_section = {"enabled": True, "takeovers": takeovers}
        wall = time.monotonic() - t0
        window_done = len(completed)
        tps_wall = round(window_done / max(wall, 1e-9), 3)
        # fidelity vs baseline: completions over the capture's own
        # duration is the comparable rate (the drain tail would bias
        # the wall-clock rate low vs a steady-state window)
        tps_window = round(window_done / max(dur_s, 1e-9), 3)
        drift = None
        if orig_tps:
            drift = round(100.0 * (tps_window - orig_tps) / orig_tps, 1)

        timeline = None
        phase_drift = None
        if not no_trace and trace_dir.exists():
            try:
                timeline = _timeline_summary(trace_dir)
            except Exception as e:  # timeline is fidelity evidence,
                timeline = {"error": str(e)}  # never a replay failure
            base_p95 = baseline.get("phase_p95_ms") or {}
            got = (timeline or {}).get("fleet_phases_ms") or {}
            if base_p95 and got:
                phase_drift = {
                    ph: round(got[ph]["p95"] - v, 1)
                    for ph, v in base_p95.items()
                    if isinstance(got.get(ph), dict)
                    and got[ph].get("p95") is not None}

        joiner = watch.agg.audit
        audit_status = joiner.status()
        confirmed = [{k: d.get(k) for k in
                      ("class", "ns", "peer_a", "peer_b", "detail")}
                     for d in joiner.divergences]
        result = {
            "label": label,
            "capture_source": capture.get("source"),
            "fleet": dict(fleet),
            "solver": solver,
            "shards": shards,
            "federation": federation,
            "ha": ha_section,
            "injected": injected,
            "world_injected": world_injected,
            "expected": len(expected),
            "completed": len(completed),
            "completed_ids": completed,
            "missing": missing,
            "extra_done": extra_done,
            "done_dups": sim.done_dups,
            "mgr_completed": mgr_completed,
            "mgr_dispatched": mgr_dispatched,
            "completion_ratio": round(
                len(completed) / max(1, len(expected)), 4),
            "drained": drained,
            "wall_s": round(wall, 1),
            "inject_wall_s": round(inject_wall_s, 1),
            "tasks_per_s": tps_wall,
            "window_tasks_per_s": tps_window,
            "baseline": baseline or None,
            "drift": {"tasks_per_s_pct": drift,
                      "phase_p95_ms": phase_drift},
            "digests": _final_digests(joiner),
            "audit": {"verdict": audit_status["verdict"],
                      "joins": audit_status["joins"],
                      "beacons": audit_status["beacons"],
                      "active": audit_status["active"],
                      "confirmed": confirmed,
                      # peer -> {proc, ns, epoch}: the chaos judge maps
                      # a divergence record's peer id to its role
                      "epochs": audit_status["epochs"]},
            "sim": sim.stats(),
            "world": {"updates_seen": sim.world_updates,
                      "toggles_accepted": sim.world_accepted,
                      "toggles_rejected": sim.world_rejected},
            "chaos": (chaos.summary() if chaos is not None else None),
            "chaos_notes": list(ctx.notes),
            # the outcome contract: every captured task completed (none
            # lost), no id the capture never issued completed, and the
            # system of record never double-counted.  Pool-side
            # done_dups stays EVIDENCE, not a failure: the positional-
            # done/goal-exchange race double-delivers occasionally by
            # reference design, and the manager's ledger dedups it.
            "ok": (not missing and not extra_done
                   and mgr_completed <= len(expected)),
        }
        replay_beacon(final=True, extra={
            "drift_pct": drift,
            "phase_p95_delta_ms": phase_drift})
        if timeline is not None:
            result["timeline"] = timeline
        return result
    finally:
        for obj in (sim, watch):
            if obj is not None:
                obj.close()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=3)
            except subprocess.TimeoutExpired:
                p.kill()
        if pool is not None:
            pool.close()
        for log in logs:
            log.close()
        # dump the in-process ring into the replay's log dir (same
        # rationale as run_rung: the atexit dump fires after the env
        # restore and would strand the evidence elsewhere)
        _rec = _flightrec.get_recorder()
        _flightrec.dump(str(log_dir / f"{_rec.proc}-{_rec.pid}"
                                      ".flight.jsonl"),
                        reason="replay_teardown")
        os.environ.clear()
        os.environ.update(saved_env)
        _trace.configure(proc="simfleet")
        _events.configure("simfleet")


def run_tenant_smoke(args) -> int:
    """ISSUE 9 satellite (ROADMAP item 2 remaining headroom): admit N
    tenants DYNAMICALLY through the live ``solver.admit`` tenant_hello
    flow — orchestrator-style, the way a real control plane would — and
    prove each namespaced fleet completes tasks on the one solverd.
    tenant_scaling.py pre-registers via ``--tenants``; this path runs
    solverd with ``--multi-tenant`` ONLY, so admission happens on the
    wire."""
    ensure_built()
    tenants = [f"ft{i}" for i in range(args.tenants)]
    port = buspool.free_port()
    log_dir = Path(args.log_dir) / f"tenant_smoke_{args.tenants}"
    log_dir.mkdir(parents=True, exist_ok=True)
    procs, logs = [], []

    def spawn(name, cmd, stdin=None, env=None):
        log = open(log_dir / f"{name}.log", "w")
        logs.append(log)
        p = subprocess.Popen(cmd, stdin=stdin, stdout=log,
                             stderr=subprocess.STDOUT,
                             env=dict(os.environ, **(env or {})))
        procs.append(p)
        return p

    pool = orch = None
    pools = {}
    try:
        pool = buspool.BusPool(BUILD_DIR / "mapd_bus", num_shards=1,
                               home_port=port, spawn=spawn)
        os.environ.update(pool.env())
        time.sleep(0.3)
        sd = spawn("solverd",
                   [sys.executable, "-m",
                    "p2p_distributed_tswap_tpu.runtime.solverd",
                    "--port", str(port), "--map", args.map_file,
                    *(["--cpu"] if args.solverd_cpu else []),
                    "--multi-tenant",
                    "--max-tenants", str(args.tenants)])
        if not wait_for_log(log_dir / "solverd.log", "solverd up", 600,
                            proc=sd):
            raise RuntimeError("solverd never became ready")
        # the orchestrator announces each tenant and waits for its
        # welcome BEFORE spawning the fleet — plan_requests published
        # into an unsubscribed topic would be lost
        orch = BusClient(port=port, peer_id="fleetsim-orch")
        orch.subscribe("solver.admit")
        welcomed = set()
        for ns in tenants:
            orch.publish("solver.admit",
                         {"type": "tenant_hello", "ns": ns})
        deadline = time.monotonic() + 30.0
        while len(welcomed) < len(tenants) \
                and time.monotonic() < deadline:
            f = orch.recv(timeout=0.5)
            if f and f.get("op") == "msg":
                d = f.get("data") or {}
                if d.get("type") == "tenant_welcome":
                    welcomed.add(d.get("ns"))
        if welcomed != set(tenants):
            print(f"tenant smoke FAILED: welcomes {sorted(welcomed)} != "
                  f"{tenants}", flush=True)
            return 1
        print(f"tenant smoke: {len(welcomed)} tenants admitted via "
              "solver.admit", flush=True)
        mgrs = {}
        for ns in tenants:
            mgrs[ns] = spawn(
                f"manager_{ns}",
                [str(BUILD_DIR / "mapd_manager_centralized"),
                 "--port", str(port), "--map", args.map_file,
                 "--solver", "tpu",
                 "--max-tracked-agents", str(args.agents + 8),
                 "--seed", str(args.seed)],
                stdin=subprocess.PIPE, env={"JG_BUS_NS": ns})
        time.sleep(0.5)
        for i, ns in enumerate(tenants):
            pools[ns] = SimAgentPool(args.agents, args.side, port=port,
                                     seed=i + 1, peer_id=f"sim-{ns}",
                                     namespace=ns)
        for p in pools.values():
            p.heartbeat_all()

        def pump_all(budget_s: float) -> None:
            end = time.monotonic() + budget_s
            while time.monotonic() < end:
                for p in pools.values():
                    p.pump(0.05)

        pump_all(2.0)
        for m in mgrs.values():
            m.stdin.write(f"tasks {args.agents}\n".encode())
            m.stdin.flush()
        pump_all(args.settle + args.window)
        done = {ns: p.done_count for ns, p in pools.items()}
        ok = all(v >= 1 for v in done.values())
        print(f"tenant smoke {'OK' if ok else 'FAILED'}: dynamic "
              f"admission + per-tenant dones {done}", flush=True)
        return 0 if ok else 1
    finally:
        for p in pools.values():
            p.close()
        if orch is not None:
            orch.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=3)
            except subprocess.TimeoutExpired:
                p.kill()
        if pool is not None:
            pool.close()
        for log in logs:
            log.close()
        os.environ.pop(buspool.SHARD_PORTS_ENV, None)


def write_replay_artifact(out: Path, res: dict, capture_path) -> None:
    """One replay's record (json + md): outcome ledger, final digests,
    fidelity drift, audit verdict."""
    out.parent.mkdir(parents=True, exist_ok=True)
    doc = {"experiment": "fleetsim replay: captured window re-driven "
                         "open-loop as a deterministic load",
           "capture": str(capture_path),
           "replay": res}
    out.write_text(json.dumps(doc, indent=2) + "\n")
    dg = res["digests"]
    drift = res.get("drift") or {}
    md = [f"# fleetsim replay — {capture_path}", "",
          f"- outcome: **{res['completed']}/{res['expected']} tasks "
          f"completed**, {len(res['missing'])} missing, "
          f"{res['done_dups']} duplicated "
          f"({'OK' if res['ok'] else 'FAILED'})",
          f"- fidelity: {res['window_tasks_per_s']} tasks/s vs original "
          f"{(res.get('baseline') or {}).get('tasks_per_s')} "
          f"(drift {drift.get('tasks_per_s_pct')}%)",
          f"- audit: {res['audit']['verdict']} "
          f"({len(res['audit']['confirmed'])} confirmed divergence(s))",
          "", "| digest | value | count | seq | epoch |", "|---|---|---|---|---|"]
    for k, v in dg.items():
        md.append(f"| {k} | `{v['digest']}` | {v['count']} | {v['seq']} "
                  f"| {v['epoch']} |")
    out.with_name(out.name + ".md").write_text("\n".join(md) + "\n")


def write_artifact(out: Path, doc: dict) -> None:
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    md = [f"# fleetsim — {doc['experiment']}", ""]
    fl = doc.get("federation_ladder")
    if fl:
        md += ["## federation scaling ladder", "",
               f"aggregate tasks/s monotone with region count: "
               f"**{fl['monotone_tasks_per_s']}**", "",
               "| regions | pairs | tasks/s | completion | handoffs "
               "sent/acked | dup dropped |", "|---|---|---|---|---|---|"]
        for r in fl["rungs"]:
            md.append(
                f"| {r['regions']} | {r['region_count']} "
                f"| {r['tasks_per_s']} | {r['completion_ratio']} "
                f"| {r.get('handoffs_sent', '-')}"
                f"/{r.get('handoffs_acked', '-')} "
                f"| {r.get('handoffs_dup_dropped', '-')} |")
        md.append("")
    for rung in doc["rungs"]:
        md.append(f"### rung: {rung['agents']} agents @ "
                  f"{rung['tick_ms']} ms tick, {rung['shards']} bus "
                  f"shard(s) ({rung['mode']} loop, {rung['solver']}"
                  + (f", regions {rung['regions']}"
                     if rung.get("regions") else "") + ")")
        md.append("")
        md.append(f"- window: {rung['window_s']} s — "
                  f"{rung['window_tasks_completed']} completed / "
                  f"{rung['window_tasks_dispatched']} dispatched "
                  f"(fleet tasks/s "
                  f"{rung['signals'].get('fleet.tasks_per_s', '-')})")
        md.append("")
        md.append(_slo.render_md(rung["slo"]))
    if doc.get("saturation") is not None:
        s = doc["saturation"]
        md.append("## saturation search")
        md.append("")
        md.append(f"- max sustainable: **{s['max_sustainable_tasks_per_s']}"
                  f" tasks/s** at {s['max_sustainable_agents']} agents"
                  f" @ {s['max_sustainable_tick_ms']} ms tick"
                  if s.get("max_sustainable_tasks_per_s") is not None
                  else "- no rung passed the spec")
        if s.get("breached_at") is not None:
            md.append(f"- first breach: {s['breached_at']} — "
                      f"SLO(s) {', '.join(s['breached_slos'])}"
                      + (f", breaching phase {s['breaching_phase']}"
                         if s.get("breaching_phase") else ""))
        md.append("")
    out.with_name(out.name + ".md").write_text("\n".join(md) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--agents", type=int, default=200)
    ap.add_argument("--side", type=int, default=96,
                    help="empty square map side (96 puts 1000 agents at "
                         "~11%% density)")
    ap.add_argument("--shards", type=int,
                    default=int(os.environ.get("JG_BUS_SHARDS", "1") or 1),
                    help="busd pool shards (the federated plane)")
    ap.add_argument("--tick-ms", type=int, default=250,
                    help="manager planning interval")
    ap.add_argument("--regions", default=None,
                    help="federated world regions (ISSUE 14): a CxR "
                         "spec (e.g. 2x1) brings up one (manager"
                         "[, solverd]) pair per region on the shared "
                         "bus pool; unset/1 = single-pair fleet")
    ap.add_argument("--region-ladder", default=None,
                    help="comma list of region specs (e.g. 1,2x1,2x2): "
                         "run the SAME workload through each federation "
                         "size and record aggregate tasks/s per rung — "
                         "the scaling artifact mode")
    ap.add_argument("--mode", choices=["closed", "open"], default="closed",
                    help="closed: one task per agent, manager refills on "
                         "done (peak sustainable); open: inject --rate "
                         "tasks/s regardless of completion")
    ap.add_argument("--rate", type=float, default=10.0,
                    help="open-loop injection rate (tasks/s)")
    ap.add_argument("--shape", default="none",
                    choices=["none", "ramp", "flash", "storm"],
                    help="open-loop traffic shape (ISSUE 16): diurnal "
                         "ramp / flash crowd / tenant arrival storm; "
                         "'none' = constant --rate (legacy wire)")
    ap.add_argument("--shape-peak", type=float, default=None,
                    help="shape peak rate tasks/s (default 4x --rate)")
    ap.add_argument("--shape-period-s", type=float, default=None,
                    help="shape period seconds (default settle+window)")
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--settle", type=float, default=45.0,
                    help="warmup before the window (first completions "
                         "need ~one task duration)")
    ap.add_argument("--ramp-s", type=float, default=20.0,
                    help="closed-loop fill ramp (chunked task injection; "
                         "clamped to settle/2)")
    ap.add_argument("--heartbeat-s", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--solver", choices=["native", "tpu"], default="native")
    ap.add_argument("--solverd-cpu", action="store_true",
                    help="run solverd on the JAX CPU backend (--cpu): "
                         "CPU smokes and tests; without it solverd runs "
                         "on the accelerator or fails")
    ap.add_argument("--spec", default=None,
                    help="SLO spec JSON file (default: obs/slo.py "
                         "rated-load spec)")
    ap.add_argument("--saturate", default=None,
                    help="comma list of agent counts: stepped-load "
                         "search, stop at first SLO breach")
    ap.add_argument("--saturate-ticks", default=None,
                    help="comma list of tick periods (ms) at fixed "
                         "--agents: rate-laddered search")
    ap.add_argument("--keep-going", action="store_true",
                    help="saturation search: run EVERY ladder rung even "
                         "past the first breach (the committed-artifact "
                         "mode: breached rungs stay in the record with "
                         "their verdicts and breaching phases)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--log-dir", default="/tmp/fleetsim_logs")
    ap.add_argument("--capture", default=None, metavar="FILE",
                    help="record this run's traffic as a versioned "
                         "capture1 artifact (ISSUE 11): task ids + "
                         "arrival offsets + endpoints, accepted world "
                         "toggles, fleet config, baseline signals — "
                         "replayable via --replay")
    ap.add_argument("--replay", default=None, metavar="FILE",
                    help="re-drive a capture1 file open-loop as a "
                         "deterministic load (same task ids, arrival "
                         "offsets, world toggles) and judge the "
                         "outcome: exit 0 iff every captured task "
                         "completed, nothing uncaptured completed, and "
                         "the manager ledger never double-counted")
    ap.add_argument("--replay-solver", choices=["native", "tpu"],
                    default=None,
                    help="override the capture's solver for --replay")
    ap.add_argument("--replay-shards", type=int, default=None,
                    help="override the capture's bus shard count for "
                         "--replay")
    ap.add_argument("--replay-drain-s", type=float, default=None,
                    help="post-injection completion budget (default: "
                         "max(30, capture duration))")
    ap.add_argument("--replay-regions", default=None,
                    help="replay the capture through a federated CxR "
                         "fleet (tasks routed to their pickup region's "
                         "manager)")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip JG_TRACE (phase-attribution SLOs read "
                         "unknown)")
    ap.add_argument("--world-toggle-cells", type=int, default=0,
                    help="dynamic worlds (ISSUE 9): close a random "
                         "N-cell wall every --world-toggle-every "
                         "seconds (reopening the previous one); 0 = "
                         "static world")
    ap.add_argument("--world-toggle-every", type=float, default=6.0)
    ap.add_argument("--tenants", type=int, default=0,
                    help="dynamic-admission smoke (ISSUE 9 satellite): "
                         "N namespaced fleets admitted LIVE through the "
                         "solver.admit tenant_hello flow against one "
                         "--multi-tenant solverd (no pre-registration); "
                         "exit 0 iff every tenant gets a welcome and "
                         "completes >= 1 task")
    args = ap.parse_args(argv)

    if args.replay:
        try:
            capture = _capture.load(args.replay)
        except _capture.CaptureError as e:
            print(f"fleetsim: cannot replay {args.replay}: {e}",
                  file=sys.stderr)
            return 2
        res = run_replay(capture, args.log_dir,
                         solver=args.replay_solver,
                         shards=args.replay_shards,
                         no_trace=args.no_trace,
                         drain_s=args.replay_drain_s,
                         regions=args.replay_regions,
                         solverd_cpu=args.solverd_cpu)
        print(json.dumps({k: res[k] for k in
                          ("expected", "completed", "missing",
                           "extra_done", "done_dups", "mgr_completed",
                           "window_tasks_per_s", "drift", "ok")}),
              flush=True)
        dg = res["digests"]
        print("replay digests: " + ", ".join(
            f"{k}={v['digest']}/{v['count']}" for k, v in dg.items()),
            flush=True)
        if args.out:
            write_replay_artifact(Path(args.out), res, args.replay)
        return 0 if res["ok"] else 1

    if args.tenants >= 1:
        args.map_file = f"/tmp/fleetsim_{args.side}.map.txt"
        Path(args.map_file).write_text(
            "\n".join(["." * args.side] * args.side) + "\n")
        return run_tenant_smoke(args)

    args.map_file = f"/tmp/fleetsim_{args.side}.map.txt"
    Path(args.map_file).write_text(
        "\n".join(["." * args.side] * args.side) + "\n")
    spec = _slo.load_spec(args.spec)

    if args.region_ladder:
        # federation scaling ladder (ISSUE 14): the SAME workload driven
        # through 1, 2, ... region pairs — the artifact behind
        # results/federation_r15.json (aggregate tasks/s must rise
        # monotonically on a workload that saturates one manager)
        rungs = []
        ladder = []
        for rspec in [r.strip() for r in args.region_ladder.split(",")
                      if r.strip()]:
            args.regions = None if rspec in ("", "1", "1x1") else rspec
            cols, rows, total = _fed_spec(args)
            print(f"fleetsim: federation rung {cols}x{rows} "
                  f"({total} region pair(s))", flush=True)
            rung = run_rung(args, args.agents, args.tick_ms, spec)
            rungs.append(rung)
            fed = rung.get("federation") or {}
            ladder.append({
                "regions": f"{cols}x{rows}",
                "region_count": total,
                "tasks_per_s": rung["signals"].get("fleet.tasks_per_s"),
                "completion_ratio": rung["signals"].get(
                    "fleet.completion_ratio"),
                "handoffs_sent": fed.get("handoffs_sent"),
                "handoffs_acked": fed.get("handoffs_acked"),
                "handoffs_dup_dropped": fed.get("handoffs_dup_dropped"),
            })
            print(json.dumps(ladder[-1]), flush=True)
        tps = [r["tasks_per_s"] for r in ladder]
        monotone = (all(v is not None for v in tps)
                    and all(b >= a for a, b in zip(tps, tps[1:])))
        doc = {
            "experiment": "federated world regions: aggregate tasks/s "
                          "vs region count on one saturating workload",
            "spec": spec,
            "rungs": rungs,
            "saturation": None,
            "federation_ladder": {"rungs": ladder,
                                  "monotone_tasks_per_s": monotone},
        }
        print(json.dumps({"ladder": ladder, "monotone": monotone}),
              flush=True)
        if args.out:
            write_artifact(Path(args.out), doc)
        return 0 if monotone else 1

    rungs = []
    saturation = None
    if args.saturate or args.saturate_ticks:
        if args.saturate:
            ladder = [(int(n), args.tick_ms)
                      for n in args.saturate.split(",")]
        else:
            ladder = [(args.agents, int(t))
                      for t in args.saturate_ticks.split(",")]
        last_pass = None
        breach = None
        for agents, tick_ms in ladder:
            print(f"fleetsim: rung {agents} agents @ {tick_ms} ms tick",
                  flush=True)
            rung = run_rung(args, agents, tick_ms, spec)
            rungs.append(rung)
            print(json.dumps({k: rung[k] for k in
                              ("agents", "tick_ms", "signals")}),
                  flush=True)
            print(_slo.render_line(rung["slo"]), flush=True)
            if rung["slo"]["ok"]:
                last_pass = rung
            elif breach is None:
                breach = rung
                if not args.keep_going:
                    break  # stepped-load search stops at the first breach
        breaching_phase = None
        if breach is not None:
            for v in breach["slo"]["verdicts"]:
                if v["status"] == "fail" and v.get("breaching_phase"):
                    breaching_phase = v["breaching_phase"]
                    break
        saturation = {
            "ladder": [{"agents": a, "tick_ms": t} for a, t in ladder],
            "max_sustainable_tasks_per_s":
                last_pass["signals"].get("fleet.tasks_per_s")
                if last_pass else None,
            "max_sustainable_agents":
                last_pass["agents"] if last_pass else None,
            "max_sustainable_tick_ms":
                last_pass["tick_ms"] if last_pass else None,
            "breached_at": (f"{breach['agents']} agents @ "
                            f"{breach['tick_ms']} ms"
                            if breach else None),
            "breached_slos": (breach["slo"]["failed"]
                              + breach["slo"]["unknown"])
            if breach else [],
            "breaching_phase": breaching_phase,
        }
    else:
        rung = run_rung(args, args.agents, args.tick_ms, spec)
        rungs.append(rung)
        print(_slo.render_line(rung["slo"]), flush=True)

    doc = {
        "experiment": "fleetsim load rehearsal: simulated wire-faithful "
                      "agent pool vs sharded bus + centralized manager",
        "spec": spec,
        "rungs": rungs,
        "saturation": saturation,
    }
    print(json.dumps({"rungs": len(rungs),
                      "ok": all(r["slo"]["ok"] for r in rungs),
                      "saturation": saturation}), flush=True)
    if args.out:
        write_artifact(Path(args.out), doc)
    if saturation is not None:
        return 0 if saturation["max_sustainable_agents"] is not None else 1
    return _slo.exit_code(rungs[0]["slo"])


if __name__ == "__main__":
    sys.exit(main())
