"""obs/ span tracer unit contract: nesting, thread safety, disabled-mode
no-op, Chrome trace-event JSONL schema round-trip, and heartbeat/stats-dump
emission from one in-process solverd tick (the tentpole's acceptance
surface, without any fleet processes)."""

import json
import sys
import threading
from pathlib import Path

import pytest

from p2p_distributed_tswap_tpu.core.grid import Grid
from p2p_distributed_tswap_tpu.obs import HeartbeatWriter, trace
from p2p_distributed_tswap_tpu.obs.trace import Tracer

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "analysis"))
import trace_report  # noqa: E402


@pytest.fixture()
def tracer(tmp_path, monkeypatch):
    """Fresh enabled global tracer per test, flushing into tmp_path;
    restore the disabled default after."""
    monkeypatch.setenv("JG_TRACE_DIR", str(tmp_path))
    t = trace.configure(enabled=True, proc="test")
    yield t
    trace.configure(enabled=False)


def test_span_nesting_parent_attribution(tracer):
    with trace.span("outer"):
        with trace.span("inner"):
            with trace.span("leaf"):
                pass
        with trace.span("inner2"):
            pass
    evs = {e["name"]: e for e in tracer._drain() if e["ph"] == "X"}
    assert set(evs) == {"outer", "inner", "inner2", "leaf"}
    assert "parent" not in evs["outer"]["args"]
    assert evs["inner"]["args"]["parent"] == "outer"
    assert evs["inner2"]["args"]["parent"] == "outer"
    assert evs["leaf"]["args"]["parent"] == "inner"
    # children are contained in the parent's [ts, ts+dur] window
    o = evs["outer"]
    for child in ("inner", "inner2", "leaf"):
        c = evs[child]
        assert o["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= o["ts"] + o["dur"] + 1  # µs rounding


def test_thread_safety_no_cross_thread_leak(tracer):
    """Spans from concurrent threads must neither corrupt the ring nor
    inherit parents across threads (nesting stacks are thread-local)."""
    N_THREADS, N_SPANS = 8, 200
    errs = []

    def worker(k):
        try:
            for i in range(N_SPANS):
                with trace.span(f"t{k}"):
                    with trace.span(f"t{k}.child"):
                        trace.count(f"c{k}")
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    evs = [e for e in tracer._drain() if e["ph"] == "X"]
    assert len(evs) == N_THREADS * N_SPANS * 2
    for e in evs:
        if e["name"].endswith(".child"):
            assert e["args"]["parent"] == e["name"][:-6]
        else:
            assert "parent" not in e["args"]
    snap = tracer.snapshot()
    assert all(snap["counters"][f"c{k}"] == N_SPANS
               for k in range(N_THREADS))


def test_disabled_mode_is_noop(tmp_path):
    """Disabled tracing: spans/instants/flush are no-ops.  Counters and
    gauges are LIVE metrics since the unified registry (obs/registry.py)
    and keep counting either way — beacons and stats dumps must work
    without JG_TRACE."""
    t = trace.configure(enabled=False, proc="test")
    null_span = trace.span("anything")
    assert trace.span("other") is null_span  # one shared object, no alloc
    with null_span:
        trace.count("x")
        trace.gauge("g", 1.0)
        trace.instant("i")
    assert t.snapshot()["counters"] == {"x": 1}  # registry-backed, always on
    assert t.snapshot()["gauges"] == {"g": 1.0}
    assert t.snapshot()["buffered_events"] == 0  # the instant was dropped
    assert trace.flush(str(tmp_path / "t.jsonl")) is None
    assert not (tmp_path / "t.jsonl").exists()
    trace.configure(enabled=False)  # fresh registry epoch for later tests


def _host_events(logdir) -> set:
    """Names of the events on the host planes of the newest xplane."""
    import glob
    import os

    import jax

    f = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                      "*.xplane.pb")))[-1]
    pd = jax.profiler.ProfileData.from_file(f)
    return {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_span_lands_on_the_device_profilers_host_plane(tmp_path):
    """Under ``jax.profiler`` an enabled span is also a TraceAnnotation:
    it lands on the xplane's host plane, on the device ops' clock.  A
    disabled span stays the shared no-op and lands nowhere."""
    import jax
    import jax.numpy as jnp

    t = trace.configure(enabled=True, proc="test")
    try:
        jax.profiler.start_trace(str(tmp_path / "on"))
        try:
            with trace.span("solverd.tick", seq=1):
                with trace.span("solverd.scatter"):
                    jnp.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        names = _host_events(tmp_path / "on")
        assert {"solverd.tick", "solverd.scatter"} <= names
        # the span's own event is unchanged
        assert [e["name"] for e in t._drain() if e["ph"] == "X"] == [
            "solverd.scatter", "solverd.tick"]

        trace.configure(enabled=False)
        jax.profiler.start_trace(str(tmp_path / "off"))
        try:
            with trace.span("solverd.tick"):
                jnp.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        assert "solverd.tick" not in _host_events(tmp_path / "off")
    finally:
        trace.configure(enabled=False)


def test_ring_buffer_bounded():
    t = Tracer(proc="ring", enabled=True, capacity=16)
    for i in range(100):
        with t.span(f"s{i}"):
            pass
    evs = [e for e in t._drain() if e["ph"] == "X"]
    assert len(evs) == 16
    assert evs[-1]["name"] == "s99"  # newest kept


def test_jsonl_schema_round_trip(tracer, tmp_path):
    with trace.span("alpha", k=1):
        with trace.span("beta"):
            pass
    trace.count("hits", 3)
    trace.instant("marker", why="test")
    path = tmp_path / "test.trace.jsonl"
    assert trace.flush(str(path)) == str(path)

    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines[0] == {"name": "process_name", "ph": "M",
                        "pid": tracer.pid, "args": {"name": "test"}}
    by_ph = {}
    for ev in lines[1:]:
        by_ph.setdefault(ev["ph"], []).append(ev)
    assert {e["name"] for e in by_ph["X"]} == {"alpha", "beta"}
    for e in by_ph["X"]:
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["pid"] == tracer.pid
    assert by_ph["C"][0] == {"name": "hits", "ph": "C",
                             "ts": by_ph["C"][0]["ts"],
                             "pid": tracer.pid, "args": {"value": 3}}
    assert by_ph["i"][0]["args"] == {"why": "test"}

    # ...and the report tool consumes exactly what the tracer wrote
    report = trace_report.build_report(trace_report.load_events([str(path)]))
    assert report["processes"] == ["test"]
    assert report["spans"]["alpha"]["count"] == 1
    assert report["counters"]["test"]["hits"] == 3

    # flush drained the ring: a second flush appends only the cumulative
    # counter snapshot (by design — the timeline of counter values), no
    # replayed spans
    n = len(lines)
    trace.flush(str(path))
    extra = [json.loads(ln)
             for ln in path.read_text().splitlines()[n:]]
    assert extra and all(e["ph"] == "C" for e in extra)


def test_percentiles():
    evs = [{"name": "s", "ph": "X", "ts": i, "dur": (i + 1) * 1000,
            "pid": 1, "args": {}} for i in range(100)]
    st = trace_report.build_report(evs)["spans"]["s"]
    assert st["count"] == 100
    assert st["p50_ms"] == 51.0
    assert st["p95_ms"] == 96.0
    assert st["p99_ms"] == 100.0
    assert st["max_ms"] == 100.0


def test_solverd_tick_heartbeat_and_stats(tracer, tmp_path):
    """One in-process solverd tick: heartbeat line lands with per-phase ms,
    the tick span tree lands in the trace, and the stats dump carries the
    cache/recompile counters."""
    from p2p_distributed_tswap_tpu.runtime.solverd import (
        PlanService, TickRunner)

    grid = Grid.default()
    hb_path = tmp_path / "solverd.heartbeat.jsonl"
    runner = TickRunner(PlanService(grid, capacity_min=4), grid,
                        heartbeat=HeartbeatWriter(str(hb_path)))
    req = {"type": "plan_request", "seq": 7, "agents": [
        {"peer_id": "a", "pos": [1, 1], "goal": [5, 1]},
        {"peer_id": "b", "pos": [3, 3], "goal": [1, 3]},
    ]}
    resp = runner.handle(req)
    assert resp["type"] == "plan_response" and resp["seq"] == 7
    assert len(resp["moves"]) == 2

    hb_lines = hb_path.read_text().splitlines()
    assert len(hb_lines) == 1
    hb = json.loads(hb_lines[0])
    assert hb["tick"] == 1 and hb["seq"] == 7 and hb["agents"] == 2
    for phase in ("decode", "cache_lookup", "field_sweep", "step_dispatch",
                  "device_sync", "encode", "total"):
        assert phase in hb["ms"], phase
    assert hb["budget_ms"] == 500.0
    # both goals were fresh: miss counters flow into the heartbeat
    assert hb["counters"]["solverd.field_cache_misses"] == 2

    stats = runner.stats()
    assert stats["service"]["ticks"] == 1
    assert stats["service"]["cache_misses"] == 2
    assert stats["service"]["cache_hits"] == 0
    assert stats["service"]["cached_fields"] == 2

    # a second tick with the same goals is all cache hits
    runner.handle({**req, "seq": 8})
    assert runner.stats()["service"]["cache_hits"] >= 2

    # the tick span tree made it into the trace (handle() flushed it)
    report = trace_report.build_report(
        trace_report.load_events([tracer.default_path("trace")]))
    assert report["spans"]["solverd.tick"]["count"] == 2
    assert report["budget"]["solverd.tick"]["ticks"] == 2
    assert "solverd.field_sweep" in report["budget"]["solverd.tick"]["phases"]
