"""Dynamic worlds (ISSUE 9): world_update wire, solverd repair engine,
queue fairness, kill-switch pins, and the mid-run wall-close e2e.

Unit layers run pure-Python/CPU; the live tests spawn busd + the C++
manager (pin: the world1 cap and every world frame vanish with
JG_DYNAMIC_WORLD=0) and — marked slow — a full fleet where a wall closes
mid-run and every in-flight task still completes.
"""

import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from p2p_distributed_tswap_tpu.core.grid import Grid
from p2p_distributed_tswap_tpu.obs import registry as _reg
from p2p_distributed_tswap_tpu.ops import distance, field_repair
from p2p_distributed_tswap_tpu.runtime import plan_codec as pc
from p2p_distributed_tswap_tpu.runtime.solverd import (
    PlanService,
    TickRunner,
    parse_world_update,
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _svc(side=16, dynamic="1", monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setenv("JG_DYNAMIC_WORLD", dynamic)
    grid = Grid(np.ones((side, side), np.bool_))
    svc = PlanService(grid, capacity_min=4)
    svc.defer_fields = False
    return svc


def _ref_field(free_np, goal):
    d = distance.distance_fields(jnp.asarray(free_np),
                                 jnp.asarray([goal], np.int32))
    packed = distance.pack_directions(
        distance.directions_from_distance(
            d, jnp.asarray(free_np)).reshape(1, -1))
    return np.asarray(d)[0], np.asarray(packed)[0]


# -- wire parsing -----------------------------------------------------------

def test_parse_world_update_json_and_packed():
    assert parse_world_update({"toggles": [[5, 1], [9, 0]]}) == \
        [(5, True), (9, False)]
    pkt = pc.encode_world(3, [70000, 7], [1, 0])
    msg = {"codec": pc.CODEC_NAME, "data": pc.encode_b64(pkt)}
    assert parse_world_update(msg) == [(70000, True), (7, False)]
    assert parse_world_update({"toggles": "nope"}) is None
    assert parse_world_update({"toggles": [[1]]}) is None
    assert parse_world_update({"codec": pc.CODEC_NAME,
                               "data": "!!!"}) is None


# -- PlanService repair engine ---------------------------------------------

def test_apply_world_update_stay_patch_and_inline_repair(monkeypatch):
    """A toggle immediately STAY-patches every cached row (no stale
    field may point into the new wall), marks the row stale, and the
    next inline _ensure_fields repairs it bit-identically."""
    svc = _svc(monkeypatch=monkeypatch)
    w = 16
    goal = 5 * w + 5
    svc.plan([("a", 0, goal)])
    assert goal in svc.dist_mirror  # JG_DYNAMIC_WORLD=1 keeps mirrors
    toggles = [(5 * w + 4, True), (4 * w + 4, True)]
    assert svc.apply_world_update(toggles) == 2
    assert svc._is_stale(goal) and svc.world_seq == 1
    row = svc.goal_rows[goal]
    packed = np.asarray(svc.dirs[row])

    def code_at(c):
        return (packed[c >> 3] >> ((c & 7) * 4)) & 0xF

    for c, _ in toggles:
        assert code_at(c) == distance.DIR_STAY
        cy, cx = divmod(c, w)
        for k, (dx, dy) in enumerate(distance.DIR_DXDY):
            nx, ny = cx - dx, cy - dy
            if 0 <= nx < w and 0 <= ny < w:
                assert code_at(ny * w + nx) != k
    svc._ensure_fields([goal])
    assert not svc._is_stale(goal)
    ref_d, ref_p = _ref_field(svc.free_np, goal)
    np.testing.assert_array_equal(svc.dist_mirror[goal], ref_d)
    np.testing.assert_array_equal(np.asarray(svc.dirs[row]), ref_p)


def test_world_update_queues_repairs_for_live_goals(monkeypatch):
    """Pinned (live) goals enqueue cause=repair on a toggle; the idle
    window repairs them and the per-cause counters move."""
    _reg.get_registry().clear()
    svc = _svc(monkeypatch=monkeypatch)
    w = 16
    goal = 3 * w + 9
    svc.plan([("a", 2, goal)])
    svc.goal_ref[goal] = 1  # resident pin = live goal
    runner = TickRunner(svc, svc.grid)
    msg = {"type": "world_update", "world_seq": 1, "codec": pc.CODEC_NAME,
           "data": pc.encode_b64(pc.encode_world(1, [8 * w + 8], [1]))}
    assert runner.handle_world(msg) == 1
    assert svc.field_queue[goal].cause == "repair"
    svc.process_field_queue()
    assert not svc._is_stale(goal)
    ref_d, ref_p = _ref_field(svc.free_np, goal)
    np.testing.assert_array_equal(svc.dist_mirror[goal], ref_d)
    snap = _reg.snapshot()
    assert snap["counters"].get(
        'solverd.field_sweeps{cause="repair"}', 0) >= 1
    assert snap["counters"].get("solverd.field_repairs", 0) >= 1
    assert snap["counters"].get("solverd.world_updates", 0) == 1
    # a freed cell is also handled (repair back toward the original)
    assert runner.handle_world(
        {"type": "world_update", "toggles": [[8 * w + 8, 0]]}) == 1
    svc.process_field_queue()
    ref_d2, _ = _ref_field(svc.free_np, goal)
    np.testing.assert_array_equal(svc.dist_mirror[goal], ref_d2)


def test_kill_switch_ignores_updates(monkeypatch):
    _reg.get_registry().clear()
    svc = _svc(dynamic="0", monkeypatch=monkeypatch)
    runner = TickRunner(svc, svc.grid)
    before = svc.free_np.copy()
    assert runner.handle_world(
        {"type": "world_update", "toggles": [[5, 1]]}) == 0
    np.testing.assert_array_equal(svc.free_np, before)
    assert svc.world_seq == 0 and not svc.keep_dist
    assert _reg.snapshot()["counters"].get(
        "solverd.world_updates_ignored", 0) == 1


def test_lazy_mirrors_first_toggle_falls_back_to_full(monkeypatch):
    """JG_DYNAMIC_WORLD unset: no mirrors until the first accepted
    toggle, so the first repair of a pre-existing row is a counted full
    recompute — and still exact."""
    _reg.get_registry().clear()
    monkeypatch.delenv("JG_DYNAMIC_WORLD", raising=False)
    grid = Grid(np.ones((16, 16), np.bool_))
    svc = PlanService(grid, capacity_min=4)
    svc.defer_fields = False
    w = 16
    goal = 2 * w + 2
    svc.plan([("a", 5, goal)])
    assert goal not in svc.dist_mirror and not svc.keep_dist
    assert svc.apply_world_update([(9 * w + 9, True)]) == 1
    assert svc.keep_dist and svc._is_stale(goal)
    svc._ensure_fields([goal])
    assert _reg.snapshot()["counters"].get(
        "solverd.field_repair_fallbacks", 0) == 1
    ref_d, ref_p = _ref_field(svc.free_np, goal)
    np.testing.assert_array_equal(svc.dist_mirror[goal], ref_d)
    np.testing.assert_array_equal(
        np.asarray(svc.dirs[svc.goal_rows[goal]]), ref_p)


# -- queue fairness (ISSUE 9 satellite) ------------------------------------

def test_field_queue_age_bound_promotes_starved_entries(monkeypatch):
    """Sustained fresh-goal churn front-inserts every call; a prime
    entry must still be processed within the age bound instead of
    starving forever."""
    _reg.get_registry().clear()
    svc = _svc(monkeypatch=monkeypatch)
    svc.prefetch_goals([1])  # the starvation candidate (cause=prime)
    assert svc.field_queue[1].cause == "prime"
    processed_at = None
    for i in range(svc.FIELD_QUEUE_MAX_AGE + 4):
        # churn: a new waiting-agent goal jumps the queue every call
        svc._queue_goal(100 + i, "fresh_goal", front=True)
        svc.process_field_queue(max_goals=1)
        if 1 in svc.goal_rows and processed_at is None:
            processed_at = i
    assert processed_at is not None and \
        processed_at <= svc.FIELD_QUEUE_MAX_AGE + 2
    snap = _reg.snapshot()
    assert snap["counters"].get("solverd.field_queue_promotions", 0) >= 1
    assert snap["counters"].get(
        'solverd.field_sweeps{cause="prime"}', 0) >= 1
    assert snap["counters"].get(
        'solverd.field_sweeps{cause="fresh_goal"}', 0) >= 1
    # the age gauge tracked the starving entry while it waited
    assert snap["gauges"].get("solverd.field_queue_max_age", 0) >= 0


def test_queue_entry_keeps_enqueue_clock_on_upgrade(monkeypatch):
    svc = _svc(monkeypatch=monkeypatch)
    svc._queue_goal(7, "prime")
    svc.queue_clock += 5
    svc._queue_goal(7, "fresh_goal", front=True)
    e = svc.field_queue[7]
    assert e.cause == "fresh_goal" and e.enq == 0  # age preserved


# -- fused-kernel fallback --------------------------------------------------

def test_fused_env_falls_back_clean_without_tpu(monkeypatch):
    """MAPD_FUSED=1 on a CPU backend must leave direction_fields on the
    portable pipeline, bit-identically."""
    from p2p_distributed_tswap_tpu.ops import field_fused

    monkeypatch.setenv("MAPD_FUSED", "1")
    assert not field_fused.fused_eligible(64, 128)
    free = jnp.asarray(np.ones((8, 16), np.bool_))
    goals = jnp.asarray([3], jnp.int32)
    out = np.asarray(distance.direction_fields(free, goals))
    ref = np.asarray(distance.directions_from_distance(
        distance.distance_fields(free, goals), free))
    np.testing.assert_array_equal(out, ref)


# -- live pins + e2e --------------------------------------------------------

TINY16 = "\n".join(["." * 16] * 16) + "\n"


@pytest.fixture(scope="module")
def built():
    from p2p_distributed_tswap_tpu.runtime.fleet import ensure_built

    ensure_built()


def _spawn_bus(port):
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    return subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                            stdout=subprocess.DEVNULL)


@pytest.mark.parametrize("dyn", ["0", "1"])
def test_world_cap_and_frames_pinned_by_kill_switch(built, tmp_path, dyn):
    """JG_DYNAMIC_WORLD=0 keeps the static wire: plan_request caps are
    EXACTLY the pre-world set (no world1 token) and a
    world_update_request produces NO world frames at all; =1 adds the
    world1 cap and the world_update/world_update_applied pair."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    mapf = tmp_path / "t16.map.txt"
    mapf.write_text(TINY16)
    port = _free_port()
    bus = _spawn_bus(port)
    mgr = None
    try:
        time.sleep(0.3)
        env = {"JG_DYNAMIC_WORLD": dyn, "JG_TRACE_CTX": "0",
               "JG_REGION_GOSSIP": "0"}
        import os
        mgr = subprocess.Popen(
            [str(BUILD_DIR / "mapd_manager_centralized"),
             "--port", str(port), "--map", str(mapf), "--solver", "tpu"],
            stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
            env={**os.environ, **env})
        cli = BusClient(port=port, peer_id="watcher")
        cli.subscribe("solver")
        cli.subscribe("mapd")
        time.sleep(0.3)
        cli.publish("mapd", {"type": "position_update", "peer_id": "a1",
                             "position": [1, 1]})
        caps = None
        deadline = time.monotonic() + 20
        while caps is None and time.monotonic() < deadline:
            f = cli.recv(timeout=1.0)
            if f and f.get("op") == "msg":
                d = f.get("data") or {}
                if d.get("type") == "plan_request":
                    caps = d.get("caps")
        assert caps is not None, "no plan_request observed"
        if dyn == "0":
            assert caps == [pc.CODEC_NAME], caps  # byte-pinned cap set
        else:
            assert caps == [pc.CODEC_NAME, pc.WORLD_CAP], caps
        cli.publish("mapd", {"type": "world_update_request",
                             "toggles": [[9, 9, 1]]})
        frames = []
        deadline = time.monotonic() + 4
        while time.monotonic() < deadline:
            f = cli.recv(timeout=0.5)
            if f and f.get("op") == "msg":
                t = (f.get("data") or {}).get("type")
                if t in ("world_update", "world_update_applied"):
                    frames.append((f.get("topic"), t, f.get("data")))
        if dyn == "0":
            assert frames == [], frames  # static wire: nothing leaks
        else:
            kinds = {(topic, t) for topic, t, _ in frames}
            assert ("mapd", "world_update") in kinds, frames
            assert ("mapd", "world_update_applied") in kinds, frames
            assert ("solver", "world_update") in kinds, frames
            solver_wu = next(d for topic, t, d in frames
                             if topic == "solver" and t == "world_update")
            # packed plan wire -> packed world1 block
            assert solver_wu.get("codec") == pc.CODEC_NAME
            toggles = parse_world_update(solver_wu)
            assert toggles == [(9 * 16 + 9, True)]
            applied = next(d for _, t, d in frames
                           if t == "world_update_applied")
            assert applied["accepted"] == 1
        cli.close()
    finally:
        if mgr is not None:
            mgr.terminate()
        bus.terminate()


def _wait_for(predicate, timeout: float, interval: float = 0.5) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


@pytest.mark.slow
def test_wall_closes_midrun_every_inflight_task_completes(built, tmp_path):
    """ISSUE 9 acceptance (c) in miniature: a live fleet (busd + C++
    manager --solver tpu + solverd + sim agents) has a wall close
    mid-run; the repaired fields route around it and EVERY in-flight
    task completes."""
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR
    from p2p_distributed_tswap_tpu.runtime.simagent import SimAgentPool

    mapf = tmp_path / "t16.map.txt"
    mapf.write_text(TINY16)
    port = _free_port()
    bus = _spawn_bus(port)
    sd = mgr = pool = None
    sd_log = open(tmp_path / "solverd.log", "w")
    try:
        time.sleep(0.3)
        sd = subprocess.Popen(
            [sys.executable, "-m",
             "p2p_distributed_tswap_tpu.runtime.solverd",
             "--port", str(port), "--cpu", "--map", str(mapf)],
            stdout=sd_log, stderr=subprocess.STDOUT)
        from p2p_distributed_tswap_tpu.runtime.fleet import wait_for_log

        assert wait_for_log(tmp_path / "solverd.log", "solverd up", 120,
                            proc=sd)
        mgr = subprocess.Popen(
            [str(BUILD_DIR / "mapd_manager_centralized"),
             "--port", str(port), "--map", str(mapf), "--solver", "tpu",
             "--planning-interval-ms", "250"],
            stdin=subprocess.PIPE, stdout=subprocess.DEVNULL)
        time.sleep(0.5)
        n = 6
        pool = SimAgentPool(n, 16, port=port, seed=3)
        pool.heartbeat_all()
        pool.pump(1.5)
        mgr.stdin.write(f"tasks {n}\n".encode())
        mgr.stdin.flush()
        assert _wait_for(lambda: (pool.pump(0.5), pool.adopted >= n)[-1],
                         45), pool.stats()
        # mid-run: ask for a wall through the middle; the manager rejects
        # occupied/endpoint cells, so SOME of it closing is the contract
        pool.bus.publish("mapd", {
            "type": "world_update_request",
            "toggles": [[8, y, 1] for y in range(2, 14)]})
        target = pool.adopted  # every task adopted so far must finish
        assert _wait_for(
            lambda: (pool.pump(0.5), pool.done_count >= target)[-1],
            150), (pool.stats(), target)
        assert pool.world_updates >= 1  # the broadcast reached the fleet
        assert pool.world_accepted >= 1, pool.stats()
    finally:
        for p in (mgr, sd):
            if p is not None:
                p.terminate()
        if pool is not None:
            pool.close()
        bus.terminate()
        sd_log.close()
