"""Live-fleet integration tests for the C++ host runtime.

These are the automated, assertion-backed version of the reference's
shell-script-only E2E strategy (SURVEY §4: the reference's scripts assert
nothing and pass/fail is human-judged).  A tiny 12x12 map keeps journeys a
few cells long so tasks complete within CI time at the faithful 500 ms tick.
"""

import json
import os
import shutil
import socket
import subprocess
import time
from pathlib import Path

import pytest

from p2p_distributed_tswap_tpu.runtime.fleet import Fleet, ensure_built

pytestmark = pytest.mark.skipif(
    shutil.which("cmake") is None or shutil.which("ninja") is None,
    reason="C++ toolchain unavailable")

TINY_MAP = "\n".join(["." * 12] * 12) + "\n"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def built():
    ensure_built()


@pytest.fixture()
def tiny_map(tmp_path):
    p = tmp_path / "tiny.map.txt"
    p.write_text(TINY_MAP)
    return str(p)


def _load_analysis(mod: str):
    """Import an analysis/ tool by path (analysis/ is not a package)."""
    import importlib.util

    path = Path(__file__).resolve().parents[1] / "analysis" / f"{mod}.py"
    spec = importlib.util.spec_from_file_location(f"analysis_{mod}", path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


@pytest.fixture(autouse=True)
def e2e_failure_artifacts(request, tmp_path):
    """ISSUE 5 satellite: on ANY failure in this module, collect every
    process's flight-recorder ring + the tail of its log into one
    pytest-managed directory and print its path — fixture-level, so no
    per-test changes.  Fleet routes JG_FLIGHT_DIR at its log dir, and
    processes dump their rings on exit/crash, so the rings are on disk by
    the time teardown runs."""
    yield
    rep = getattr(request.node, "rep_call", None)
    if rep is None or not rep.failed:
        return
    import sys

    dest = tmp_path / "failure_artifacts"
    dest.mkdir(exist_ok=True)
    collected = 0
    for f in tmp_path.glob("**/*.flight.jsonl"):
        if dest in f.parents:
            continue
        shutil.copy(f, dest / f.name)
        collected += 1
    for f in tmp_path.glob("**/*.log"):
        if dest in f.parents:
            continue
        (dest / (f.name + ".tail")).write_text(
            f.read_text(errors="ignore")[-4000:])
        collected += 1
    # merged last-seconds readout next to the raw rings
    try:
        bb = _load_analysis("blackbox")
        metas, events = bb.load_dumps(dest)
        t_end = max((e.get("ts_ms", 0) for e in events), default=0)
        (dest / "blackbox.txt").write_text("\n".join(
            bb.render_event(e, t_end) for e in events
            if e.get("ts_ms", 0) >= t_end - 30_000))
    except Exception as e:  # artifacts must never mask the real failure
        (dest / "blackbox.txt").write_text(f"blackbox render failed: {e}")
    print(f"\n[e2e failure artifacts] {collected} file(s): {dest}",
          file=sys.stderr, flush=True)


def _wait_for(predicate, timeout: float, interval: float = 0.5) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def _count_completed(csv_path: Path) -> int:
    if not csv_path.exists():
        return 0
    return sum(1 for line in csv_path.read_text().splitlines()[1:]
               if line.endswith(",completed"))


@pytest.mark.parametrize("mode", ["decentralized", "centralized"])
def test_fleet_completes_tasks(built, tiny_map, tmp_path, mode):
    log_dir = tmp_path / "logs"
    task_csv = tmp_path / "task_metrics.csv"
    path_csv = tmp_path / "path_metrics.csv"
    with Fleet(mode, num_agents=2, port=_free_port(), map_file=tiny_map,
               log_dir=str(log_dir),
               env={"TASK_CSV_PATH": str(task_csv),
                    "PATH_CSV_PATH": str(path_csv)}) as fleet:
        time.sleep(4)  # discovery + initial positions
        fleet.command("tasks 2")

        def agents_done():
            done = 0
            for f in log_dir.glob("agent_*.log"):
                done += f.read_text(errors="ignore").count("DONE")
            return done >= 2

        completed = _wait_for(agents_done, timeout=45)
        fleet.command("metrics")
        time.sleep(1)
        fleet.quit()
        assert completed, "no task completions within 45s: " + "".join(
            f.read_text(errors="ignore")[-500:]
            for f in sorted(log_dir.glob("*.log")))

    # CSV auto-save on exit (TASK_CSV_PATH/PATH_CSV_PATH capability)
    assert task_csv.exists()
    assert _count_completed(task_csv) >= 2
    header = task_csv.read_text().splitlines()[0]
    assert header.startswith("task_id,peer_id,sent_time_ms")
    if mode == "decentralized":
        assert path_csv.exists()
        assert "duration_micros" in path_csv.read_text().splitlines()[0]


@pytest.mark.parametrize("mode", ["decentralized", "centralized"])
def test_task_requeued_on_agent_death(built, tiny_map, tmp_path, mode):
    """Kill an agent mid-task: its task must be re-queued and completed by a
    surviving agent.  The reference loses such tasks (only the peer mapping
    is cleaned, src/bin/decentralized/manager.rs:185-189) — this build
    exceeds it (VERDICT r1 item 8)."""
    log_dir = tmp_path / "logs"
    csv = tmp_path / "task_metrics.csv"
    with Fleet(mode, num_agents=3, port=_free_port(), map_file=tiny_map,
               log_dir=str(log_dir)) as fleet:
        time.sleep(4)  # discovery + initial positions
        fleet.command("tasks 3")

        manager_log = log_dir / "manager.log"

        def dispatched():
            return manager_log.read_text(errors="ignore").count("📤") >= 3

        assert _wait_for(dispatched, timeout=15), "tasks not dispatched"
        time.sleep(1.2)  # let tasks get in flight (journeys take seconds)
        victim = fleet.procs[2]  # first agent process (bus, manager, agents…)
        victim.kill()

        def initial_tasks_done():
            fleet.command(f"save {csv}")
            time.sleep(0.5)
            if not csv.exists():
                return False
            done = {int(r.split(",")[0])
                    for r in csv.read_text().splitlines()[1:]
                    if r.endswith(",completed")}
            return {1, 2, 3} <= done

        completed = _wait_for(initial_tasks_done, timeout=60, interval=2)
        log = manager_log.read_text(errors="ignore")
        fleet.quit()
        assert "re-queue" in log or "re-dispatch" in log, (
            "no re-queue observed after agent death:\n" + log[-1500:])
        assert completed, (
            "initially dispatched tasks not all completed after agent "
            "death:\n" + log[-1500:])


def test_solverd_drops_stale_requests_and_reports_recompiles(built):
    """A burst of plan_requests queued behind a slow plan: solverd must
    compute only the NEWEST (the manager discards stale seqs anyway) and
    must announce recompile stalls to the operator (VERDICT r1 weak 8)."""
    import subprocess
    import sys
    import threading

    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    port = _free_port()
    bus = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                           stdout=subprocess.DEVNULL)
    sd = None
    try:
        time.sleep(0.3)
        sd = subprocess.Popen(
            [sys.executable, "-m",
             "p2p_distributed_tswap_tpu.runtime.solverd",
             "--port", str(port), "--cpu"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        lines = []
        threading.Thread(target=lambda: [lines.append(l) for l in sd.stdout],
                         daemon=True).start()
        assert _wait_for(lambda: any("solverd up" in l for l in lines), 60), \
            lines
        cli = BusClient(port=port, peer_id="fakemgr")
        cli.subscribe("solver")
        time.sleep(0.3)
        # 30 rapid requests: whatever solverd dequeues first compiles for
        # seconds, so the rest pile up and the drain must skip straight to
        # the newest (exact batching depends on scheduling, hence ranges)
        last_seq = 30
        for seq in range(1, last_seq + 1):
            cli.publish("solver", {
                "type": "plan_request", "seq": seq,
                "agents": [{"peer_id": "a1", "pos": [1, 1],
                            "goal": [5, 5]}]})
        got = []
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and last_seq not in got:
            f = cli.recv(timeout=2.0)
            if (f and f.get("op") == "msg"
                    and (f.get("data") or {}).get("type") == "plan_response"):
                got.append(f["data"]["seq"])
        assert got and got[-1] == last_seq, (got, lines[-5:])
        assert len(got) < last_seq / 2, f"barely any drops: {got}"
        # the stdout reader thread races the bus: the response can reach
        # the client before the print lands in `lines` on a 1-core host —
        # wait for the log lines instead of asserting instantly
        assert _wait_for(lambda: any("dropped" in l for l in lines), 5), \
            lines
        assert _wait_for(
            lambda: any("recompiled step program" in l for l in lines),
            5), lines
    finally:
        if sd is not None:
            sd.terminate()
        bus.terminate()


def test_centralized_tpu_solver_fleet(built, tiny_map, tmp_path):
    """The north-star deployment shape (BASELINE.json): centralized manager
    with --solver=tpu delegating each planning tick to the JAX solver
    daemon over the bus, end to end until tasks complete.  solverd runs
    --cpu here so CI needs no accelerator — the daemon's program is
    backend-agnostic."""
    log_dir = tmp_path / "logs"
    with Fleet("centralized", num_agents=2, port=_free_port(),
               map_file=tiny_map, solver="tpu", log_dir=str(log_dir),
               solverd_args=["--cpu"]) as fleet:
        time.sleep(4)
        fleet.command("tasks 2")

        def agents_done():
            done = 0
            for f in log_dir.glob("agent_*.log"):
                done += f.read_text(errors="ignore").count("DONE")
            return done >= 2

        # generous budget: under heavy machine load solverd's responses can
        # lag whole planning ticks before the pipeline settles
        completed = _wait_for(agents_done, timeout=90)
        fleet.quit()
        solverd_log = (log_dir / "solverd.log").read_text(errors="ignore")
        assert completed, "".join(
            f.read_text(errors="ignore")[-500:]
            for f in sorted(log_dir.glob("*.log")))
        # the moves must actually have come from the daemon
        assert "solverd up" in solverd_log


def test_packed_plan_wire_live_fleet(built, tiny_map, tmp_path):
    """ISSUE 3 tentpole e2e: the default --solver=tpu wire is the packed
    codec.  A live fleet completes tasks end-to-end while every
    plan_request on the bus carries base64 packed data (no JSON agents
    arrays), responses come back packed, and after the initial snapshot
    the requests are deltas."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient
    from p2p_distributed_tswap_tpu.runtime import plan_codec as pc

    log_dir = tmp_path / "logs"
    port = _free_port()
    with Fleet("centralized", num_agents=2, port=port, map_file=tiny_map,
               solver="tpu", log_dir=str(log_dir),
               solverd_args=["--cpu"]) as fleet:
        spy = BusClient(port=port, peer_id="wire-spy")
        spy.subscribe("solver")
        # the manager plans from its first tick, so its opening snapshot
        # can go out before the spy is subscribed: ask for one on the
        # plan wire's resync path (what a daemon that lost the chain
        # does), so the spy sees a chain start
        spy.publish("solver", {"type": "plan_snapshot_request",
                               "have_seq": -1})
        time.sleep(4)
        fleet.command("tasks 2")

        kinds = []
        packed_resps = 0
        json_frames = 0
        deadline = time.monotonic() + 90

        def agents_done():
            return sum(f.read_text(errors="ignore").count("DONE")
                       for f in log_dir.glob("agent_*.log")) >= 2

        while time.monotonic() < deadline:
            f = spy.recv(timeout=1.0)
            if f and f.get("op") == "msg":
                d = f.get("data") or {}
                if d.get("type") == "plan_request":
                    if d.get("codec") == pc.CODEC_NAME:
                        kinds.append(pc.decode_b64(d["data"]).kind)
                    else:
                        json_frames += 1
                elif (d.get("type") == "plan_response"
                        and d.get("codec") == pc.CODEC_NAME):
                    packed_resps += 1
            if agents_done() and len(kinds) >= 5:
                break
        done = agents_done()
        spy.close()
        fleet.quit()
        assert done, "".join(f.read_text(errors="ignore")[-500:]
                             for f in sorted(log_dir.glob("*.log")))
    assert json_frames == 0, "legacy JSON plan_requests on a packed fleet"
    assert pc.KIND_SNAPSHOT in kinds, kinds
    first = kinds.index(pc.KIND_SNAPSHOT)
    assert pc.KIND_DELTA in kinds[first + 1:], \
        f"no delta ticks after the snapshot: {kinds}"
    assert packed_resps >= 1, "no packed plan_responses observed"


def test_json_codec_manager_interops_with_solverd(built, tiny_map,
                                                  tmp_path):
    """Caps negotiation: a JSON-only manager (JG_PLAN_CODEC=json — the
    stand-in for any plain-JSON peer) still completes tasks against the
    same solverd, which must answer on the legacy JSON wire."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient

    log_dir = tmp_path / "logs"
    port = _free_port()
    with Fleet("centralized", num_agents=2, port=port, map_file=tiny_map,
               solver="tpu", log_dir=str(log_dir),
               solverd_args=["--cpu"],
               env={"JG_PLAN_CODEC": "json"}) as fleet:
        spy = BusClient(port=port, peer_id="wire-spy")
        spy.subscribe("solver")
        time.sleep(4)
        fleet.command("tasks 2")

        json_moves = 0
        deadline = time.monotonic() + 90

        def agents_done():
            return sum(f.read_text(errors="ignore").count("DONE")
                       for f in log_dir.glob("agent_*.log")) >= 2

        while time.monotonic() < deadline:
            f = spy.recv(timeout=1.0)
            if f and f.get("op") == "msg":
                d = f.get("data") or {}
                if d.get("type") == "plan_response" and "moves" in d:
                    json_moves += 1
            if agents_done() and json_moves >= 2:
                break
        done = agents_done()
        spy.close()
        fleet.quit()
        assert done, "".join(f.read_text(errors="ignore")[-500:]
                             for f in sorted(log_dir.glob("*.log")))
    assert json_moves >= 1, "solverd never answered on the JSON wire"


def test_solverd_restart_triggers_snapshot_resync(built, tiny_map,
                                                  tmp_path):
    """Seq-gap recovery end-to-end: kill solverd mid-run and start a fresh
    one — its empty delta chain must make it publish
    plan_snapshot_request, the manager must answer with a full snapshot,
    and the fleet must keep completing tasks on the packed wire."""
    import sys

    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    log_dir = tmp_path / "logs"
    port = _free_port()
    sd2 = None
    sd2_log = None
    with Fleet("centralized", num_agents=2, port=port, map_file=tiny_map,
               solver="tpu", log_dir=str(log_dir),
               solverd_args=["--cpu"],
               env={"MAPD_SOLVER_FAILOVER_MS": "2000"}) as fleet:
        try:
            time.sleep(4)
            fleet.command("tasks 2")

            def done_count():
                return sum(f.read_text(errors="ignore").count("DONE")
                           for f in log_dir.glob("agent_*.log"))

            assert _wait_for(lambda: done_count() >= 1, timeout=60), \
                "fleet not functional before the solverd restart"
            fleet.procs[1].kill()  # [bus, solverd, manager, agents...]
            time.sleep(1.0)
            sd2_log = open(tmp_path / "solverd2.log", "w")
            sd2 = subprocess.Popen(
                [sys.executable, "-m",
                 "p2p_distributed_tswap_tpu.runtime.solverd",
                 "--port", str(port), "--map", tiny_map, "--cpu"],
                stdout=sd2_log, stderr=subprocess.STDOUT,
                cwd=str(Path(__file__).resolve().parents[1]))

            def resynced():
                mgr = (log_dir / "manager.log").read_text(errors="ignore")
                sd = (tmp_path / "solverd2.log").read_text(errors="ignore")
                return ("requested a plan snapshot" in mgr
                        and "requested full snapshot" in sd)

            assert _wait_for(resynced, timeout=60), (
                (log_dir / "manager.log").read_text(
                    errors="ignore")[-1500:]
                + (tmp_path / "solverd2.log").read_text(
                    errors="ignore")[-1500:])
            base = done_count()
            fleet.command("tasks 2")
            assert _wait_for(lambda: done_count() >= base + 2, timeout=60), (
                "no completions after the snapshot resync:\n"
                + (log_dir / "manager.log").read_text(
                    errors="ignore")[-1500:])
            fleet.quit()
        finally:
            if sd2 is not None:
                sd2.kill()
            if sd2_log is not None:
                sd2_log.close()


def test_task_requeued_on_mute_agent(built, tiny_map, tmp_path):
    """SIGSTOP an agent mid-task: its TCP stays open (no peer_left), but the
    decentralized manager's stale sweep must re-queue the task so another
    agent completes it.  The reference loses the task (and never detects
    mute peers at all)."""
    import signal as sig

    from p2p_distributed_tswap_tpu.core.config import RuntimeConfig

    log_dir = tmp_path / "logs"
    csv = tmp_path / "task_metrics.csv"
    cfg = RuntimeConfig(agent_stale_ms=3000, cleanup_interval_ms=1500)
    with Fleet("decentralized", num_agents=3, port=_free_port(),
               map_file=tiny_map, log_dir=str(log_dir),
               config=cfg) as fleet:
        time.sleep(4)
        fleet.command("tasks 3")
        manager_log = log_dir / "manager.log"
        assert _wait_for(
            lambda: manager_log.read_text(errors="ignore").count("📤") >= 3,
            timeout=15), "tasks not dispatched"
        time.sleep(1.0)
        victim = fleet.procs[2]
        victim.send_signal(sig.SIGSTOP)  # mute, not dead: no peer_left

        def initial_tasks_done():
            fleet.command(f"save {csv}")
            time.sleep(0.5)
            if not csv.exists():
                return False
            done = {int(r.split(",")[0])
                    for r in csv.read_text().splitlines()[1:]
                    if r.endswith(",completed")}
            return {1, 2, 3} <= done

        completed = _wait_for(initial_tasks_done, timeout=60, interval=2)
        log = manager_log.read_text(errors="ignore")
        victim.send_signal(sig.SIGCONT)  # let close() terminate it cleanly
        if not completed:
            # A sampled task endpoint can land on the cell the frozen body
            # occupies (time-seeded RNG, 12x12 map) — physically
            # unreachable until the victim resumes.  The property under
            # test is that the task is re-queued and never LOST, so give
            # the resumed fleet a grace period; exactly-once counting is
            # still asserted via the CSV.
            completed = _wait_for(initial_tasks_done, timeout=45, interval=2)
        fleet.quit()
        assert "silent for" in log and "re-queueing" in log, log[-4000:]
        assert completed, log[-4000:] + "".join(
            "\n== " + f.name + " ==\n" + f.read_text(errors="ignore")[-1500:]
            for f in sorted(log_dir.glob("agent_*.log")))


def test_tpu_solver_failover_to_native(built, tiny_map, tmp_path):
    """Kill the solver daemon mid-run: the manager must fail over to its
    native sequential TSWAP (logging the transition) and the fleet must
    still complete tasks — the reference has no comparable resilience
    path."""
    log_dir = tmp_path / "logs"
    with Fleet("centralized", num_agents=2, port=_free_port(),
               map_file=tiny_map, solver="tpu", log_dir=str(log_dir),
               solverd_args=["--cpu"],
               env={"MAPD_SOLVER_FAILOVER_MS": "2000"}) as fleet:
        time.sleep(4)
        fleet.procs[1].kill()  # [bus, solverd, manager, agents...]
        fleet.command("tasks 2")

        def agents_done():
            done = 0
            for f in log_dir.glob("agent_*.log"):
                done += f.read_text(errors="ignore").count("DONE")
            return done >= 2

        completed = _wait_for(agents_done, timeout=60)
        fleet.quit()
        mgr = (log_dir / "manager.log").read_text(errors="ignore")
        assert "planning natively" in mgr, mgr[-1200:]
        assert completed, mgr[-1200:]


def test_echo_probe_self_validates(built):
    """The C13 stream-demo equivalent: echo client sends random payloads and
    byte-verifies every echo (ref stream.rs:139-156 self-validation); exit 0
    only when all round-trips check out."""
    import subprocess

    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    port = _free_port()
    bus = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                           stdout=subprocess.DEVNULL)
    server = None
    try:
        time.sleep(0.3)
        server = subprocess.Popen(
            [str(BUILD_DIR / "mapd_echo"), "--server", "--port", str(port)],
            stdout=subprocess.DEVNULL)
        time.sleep(0.3)
        client = subprocess.run(
            [str(BUILD_DIR / "mapd_echo"), "--client", "--port", str(port),
             "--count", "5", "--bytes", "128", "--seed", "7"],
            capture_output=True, text=True, timeout=30)
        assert client.returncode == 0, client.stdout + client.stderr
        assert "5/5 verified" in client.stdout
    finally:
        if server is not None:
            server.terminate()
        bus.terminate()


def test_chat_probe_broadcasts(built):
    """The C13 chat/sns-demo equivalent: a line typed at one probe arrives
    at the other; /post sends the sns-style structured Post."""
    import subprocess

    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    port = _free_port()
    bus = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                           stdout=subprocess.DEVNULL)
    a = b = None
    try:
        time.sleep(0.3)
        import threading

        a = subprocess.Popen(
            [str(BUILD_DIR / "mapd_chat"), "--port", str(port),
             "--name", "alice"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        a_lines = []
        threading.Thread(target=lambda: [a_lines.append(l)
                                         for l in a.stdout],
                         daemon=True).start()
        # alice's banner prints after her connect+subscribe went out;
        # only then start bob, so his join lands on a subscribed alice
        assert _wait_for(
            lambda: any("chat probe alice" in l for l in a_lines),
            timeout=15), a_lines
        time.sleep(0.3)  # let busd process alice's sub frame
        b = subprocess.Popen(
            [str(BUILD_DIR / "mapd_chat"), "--port", str(port),
             "--name", "bob"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        # wait until ALICE SEES BOB joined (observable condition): once
        # she prints it, bob's subscription is live and the broadcast
        # cannot fan out to nobody.  (Bus-level peer ids are random — any
        # join alice sees is bob.)
        assert _wait_for(
            lambda: any("peer joined:" in l for l in a_lines),
            timeout=15), a_lines
        a.stdin.write("hello from alice\n/post status update\n/quit\n")
        a.stdin.flush()
        time.sleep(2.0)  # bob must drain the relay before his own /quit
        b.stdin.write("/quit\n")
        b.stdin.flush()
        out_b = b.communicate(timeout=10)[0]
        a.wait(timeout=10)
        assert "<alice> hello from alice" in out_b, out_b
        assert "[alice] status update" in out_b, out_b
    finally:
        for p in (a, b):
            if p is not None and p.poll() is None:
                p.kill()
        bus.terminate()


def test_manager_cli_metrics_and_reset(built, tiny_map, tmp_path):
    with Fleet("decentralized", num_agents=1, port=_free_port(),
               map_file=tiny_map, log_dir=str(tmp_path)) as fleet:
        time.sleep(3.5)
        fleet.command("tasks 1")
        time.sleep(2)
        fleet.command("metrics")
        fleet.command("reset")
        time.sleep(1)
        fleet.quit()
        log = (tmp_path / "manager.log").read_text(errors="ignore")
        assert "Task Statistics" in log
        assert "state reset" in log


def test_corridor_head_on_exchanges_complete(built, tmp_path):
    """Livelock regression (round 5): two centralized agents shuttling
    tasks on a 1-row corridor meet head-on constantly.  When the pair
    meets at EVEN separation, the native TSWAP step resolves it with a
    Rule-4 goal rotation — and the round-4 manager, which reset goals
    from tasks every tick, would rotate, retreat one cell, snap back,
    and repeat forever (the fleet-freeze flake).  With goal exchanges
    adopted as task re-assignments (adopt_goal_exchanges + Task
    re-broadcast + task_withdrawn), every encounter must make progress:
    the corridor fleet keeps completing tasks."""
    corridor = tmp_path / "corridor.map.txt"
    corridor.write_text("." * 10 + "\n")
    log_dir = tmp_path / "logs"
    csv = tmp_path / "task_metrics.csv"
    with Fleet("centralized", num_agents=2, port=_free_port(),
               map_file=str(corridor), log_dir=str(log_dir)) as fleet:
        time.sleep(3)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            fleet.command("tasks 2")
            time.sleep(3)

        fleet.command(f"save {csv}")
        time.sleep(0.5)
        done = _count_completed(csv)
        mgr = (log_dir / "manager.log").read_text(errors="ignore")
        fleet.quit()
        # a single head-on livelock caps completions near zero; healthy
        # exchange handling sustains a steady completion stream
        assert done >= 6, (
            f"only {done} completions in 60s on the corridor — head-on "
            "encounters are stalling:\n" + mgr[-1500:])


def test_corridor_head_on_decentralized_task_exchange(built, tmp_path):
    """Deadlock regression (round 5, decentralized twin of the corridor
    test): two decentralized agents meeting head-on used to exchange
    GOALS (goal_swap / target_rotation) while their tasks stayed put —
    each then walked to the other's goal and froze there forever,
    because phase transitions are positional against the task's own
    cells and the decision tick skips when pos == goal (observed live in
    the bus-restart flake: both agents heartbeating, zero arrivals).
    Exchanges now ride swap_request/swap_response carrying task+phase,
    so the task follows the heading and the corridor fleet keeps
    completing tasks through every encounter."""
    corridor = tmp_path / "corridor.map.txt"
    corridor.write_text("." * 10 + "\n")
    log_dir = tmp_path / "logs"
    csv = tmp_path / "task_metrics.csv"
    with Fleet("decentralized", num_agents=2, port=_free_port(),
               map_file=str(corridor), log_dir=str(log_dir)) as fleet:
        time.sleep(3)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            fleet.command("tasks 2")
            time.sleep(3)

        fleet.command(f"save {csv}")
        time.sleep(0.5)
        done = _count_completed(csv)
        mgr = (log_dir / "manager.log").read_text(errors="ignore")
        fleet.quit()
        assert done >= 6, (
            f"only {done} completions in 60s on the decentralized "
            "corridor — head-on exchanges are stranding tasks:\n"
            + mgr[-2500:] + "".join(
                "\n== " + f.name + " ==\n"
                + f.read_text(errors="ignore")[-1200:]
                for f in sorted(log_dir.glob("agent_*.log"))))


def test_unclaimed_task_sweep_rescues_stranded_task(built, tiny_map,
                                                    tmp_path):
    """The in-flight ledger's sweep, triggered deterministically: two
    scripted bus peers under the real manager.  Peer 1 heartbeats a
    claim for peer 2's task — the aftermath of a peer-side exchange
    whose other half was lost — so peer 1's OWN task is claimed by
    nobody.  The manager must move bookkeeping to follow the claims,
    re-queue the unclaimed task after agent_stale_ms, re-dispatch it,
    and count every task exactly once."""
    from p2p_distributed_tswap_tpu.core.config import RuntimeConfig
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient

    log_dir = tmp_path / "logs"
    csv = tmp_path / "task_metrics.csv"
    port = _free_port()
    cfg = RuntimeConfig(agent_stale_ms=4000, cleanup_interval_ms=1000)
    with Fleet("decentralized", num_agents=0, port=port, map_file=tiny_map,
               log_dir=str(log_dir), config=cfg) as fleet:
        time.sleep(1.5)
        p1 = BusClient(port=port, peer_id="py-agent-1")
        p2 = BusClient(port=port, peer_id="py-agent-2")
        for c in (p1, p2):
            c.subscribe("mapd")
        time.sleep(1.0)  # peer_joined reaches the manager
        fleet.command("tasks 2")

        tasks = {}       # peer_id -> first task id assigned by the manager
        deliveries = {}  # task id -> times a bare Task for it was received
        rescued = None   # id of the re-dispatched (swept) task
        t_end = time.monotonic() + 25
        last_beat = 0.0
        while time.monotonic() < t_end:
            now = time.monotonic()
            if now - last_beat >= 0.5:
                last_beat = now
                for cli, pos in ((p1, [1, 1]), (p2, [2, 2])):
                    beat = {"type": "position_update",
                            "peer_id": cli.peer_id, "position": pos}
                    # p1 falsely claims p2's task (severed-exchange
                    # aftermath); p2 claims its own honestly
                    if tasks.get("py-agent-2") is not None:
                        beat["busy_task"] = tasks["py-agent-2"]
                    cli.publish("mapd", beat)
            for cli in (p1, p2):
                f = cli.recv(timeout=0.1)
                if not f or f.get("op") != "msg":
                    continue
                d = f.get("data") or {}
                if "pickup" in d and d.get("peer_id") == cli.peer_id:
                    tid = d["task_id"]
                    deliveries[tid] = deliveries.get(tid, 0) + 1
                    if cli.peer_id not in tasks:
                        tasks[cli.peer_id] = tid
                    elif (tid == tasks.get("py-agent-1")
                            and deliveries[tid] >= 2):
                        # SECOND delivery of the stranded task: the sweep
                        # re-dispatched it — complete it now
                        rescued = tid
                        cli.publish("mapd", {
                            "type": "task_metric_completed",
                            "task_id": tid, "peer_id": cli.peer_id,
                            "timestamp_ms": int(time.time() * 1000)})
                        cli.publish("mapd",
                                    {"status": "done", "task_id": tid})
            if rescued is not None:
                break
        # peer 2 finishes its own task so both count exactly once
        if tasks.get("py-agent-2") is not None:
            p2.publish("mapd", {
                "type": "task_metric_completed",
                "task_id": tasks["py-agent-2"], "peer_id": "py-agent-2",
                "timestamp_ms": int(time.time() * 1000)})
            p2.publish("mapd",
                       {"status": "done", "task_id": tasks["py-agent-2"]})
        time.sleep(1.0)
        fleet.command(f"save {csv}")
        time.sleep(0.5)
        log = (log_dir / "manager.log").read_text(errors="ignore")
        p1.close()
        p2.close()
        fleet.quit()
        assert len(tasks) == 2, f"dispatch incomplete: {tasks}, log:\n" \
            + log[-2000:]
        assert "unclaimed by any peer" in log, log[-3000:]
        assert rescued == tasks["py-agent-1"], (
            f"stranded task {tasks['py-agent-1']} was never re-dispatched:\n"
            + log[-3000:])
        done_rows = [int(r.split(",")[0])
                     for r in csv.read_text().splitlines()[1:]
                     if r.endswith(",completed")]
        assert set(tasks.values()) <= set(done_rows), (csv.read_text(),
                                                       log[-2000:])
        # exactly once: one completed row per task, no double count of
        # the re-dispatched copy
        assert len(done_rows) == len(set(done_rows)), csv.read_text()


def test_bus_fault_injection_drops_one_frame(built, tmp_path):
    """The busd --drop-type knob severs exactly the first matching frame:
    with MAPD_BUS_DROP_TYPE=chat, alice's first chat line never reaches
    bob but her second does — reproducible loss for protocol tests."""
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    port = _free_port()
    env = dict(os.environ, MAPD_BUS_DROP_TYPE="chat",
               MAPD_BUS_DROP_COUNT="1")
    bus_log = open(tmp_path / "bus.log", "w")
    bus = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                           stdout=bus_log, stderr=subprocess.STDOUT,
                           env=env)
    a = b = None
    try:
        time.sleep(0.3)
        import threading
        b = subprocess.Popen(
            [str(BUILD_DIR / "mapd_chat"), "--port", str(port),
             "--name", "bob"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        b_lines = []
        threading.Thread(target=lambda: [b_lines.append(l)
                                         for l in b.stdout],
                         daemon=True).start()
        assert _wait_for(
            lambda: any("chat probe bob" in l for l in b_lines),
            timeout=15), b_lines
        time.sleep(0.3)
        a = subprocess.Popen(
            [str(BUILD_DIR / "mapd_chat"), "--port", str(port),
             "--name", "alice"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        assert _wait_for(
            lambda: any("peer joined:" in l for l in b_lines),
            timeout=15), b_lines
        a.stdin.write("dropped line\nsurviving line\n/quit\n")
        a.stdin.flush()
        assert _wait_for(
            lambda: any("surviving line" in l for l in b_lines),
            timeout=15), b_lines
        assert not any("dropped line" in l for l in b_lines), b_lines
        b.stdin.write("/quit\n")
        b.stdin.flush()
        b.wait(timeout=10)
        a.wait(timeout=10)
    finally:
        for p in (a, b):
            if p is not None and p.poll() is None:
                p.kill()
        bus.terminate()
        bus_log.close()
    log = (tmp_path / "bus.log").read_text(errors="ignore")
    assert "fault injection: dropped chat frame" in log, log[-1000:]


def test_legacy_swap_response_without_request_id_accepted(built, tiny_map,
                                                          tmp_path):
    """ADVICE r5 medium: the reference agent answers swap_request WITHOUT
    echoing request_id (agent.rs:1117-1122).  A scripted legacy peer parks
    on our agent's next hop (claiming it as its goal), waits for the
    agent's swap_request, and answers request_id-less carrying its own
    task.  The agent must ACCEPT the response — observable as its goal
    moving to the offered task's pickup — instead of silently dropping it
    and keeping a duplicate task holder on the wire."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient

    log_dir = tmp_path / "logs"
    port = _free_port()
    with Fleet("decentralized", num_agents=1, port=port, map_file=tiny_map,
               log_dir=str(log_dir)) as fleet:
        time.sleep(3.5)
        legacy = BusClient(port=port, peer_id="legacy-swapper")
        legacy.subscribe("mapd")
        fleet.command("tasks 1")

        def next_hop(pos, goal):
            # reference neighbor order, first strict improvement — same
            # next hop the agent's own BFS descent picks on an empty map
            x, y = pos
            gx, gy = goal
            d0 = abs(x - gx) + abs(y - gy)
            for dx, dy in ((0, 1), (1, 0), (0, -1), (-1, 0)):
                nx, ny = x + dx, y + dy
                if 0 <= nx < 12 and 0 <= ny < 12 \
                        and abs(nx - gx) + abs(ny - gy) < d0:
                    return [nx, ny]
            return None

        fake_pickup, fake_delivery = [10, 11], [0, 11]
        agent_id = None
        agent_pos = agent_goal = None
        swap_seen = False
        goal_adopted = False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not goal_adopted:
            f = legacy.recv(timeout=1.0)
            if not f or f.get("op") != "msg":
                continue
            d = f.get("data") or {}
            typ = d.get("type")
            if typ == "position" and d.get("peer_id") != "legacy-swapper":
                agent_id = d["peer_id"]
                agent_pos, agent_goal = d.get("pos"), d.get("goal")
                if swap_seen and agent_goal == fake_pickup:
                    goal_adopted = True
                elif not swap_seen and agent_pos and agent_goal \
                        and agent_pos != agent_goal:
                    hop = next_hop(agent_pos, agent_goal)
                    if hop:
                        # park "at our goal" on the agent's next hop: its
                        # decision tick reads Rule 3 -> swap_request to us
                        legacy.publish("mapd", {
                            "type": "position",
                            "peer_id": "legacy-swapper",
                            "pos": hop, "goal": hop,
                            "position": hop})
            elif typ == "swap_request" \
                    and d.get("to_peer") == "legacy-swapper":
                swap_seen = True
                legacy.publish("mapd", {  # NOTE: no request_id (legacy)
                    "type": "swap_response",
                    "from_peer": "legacy-swapper",
                    "to_peer": d["from_peer"],
                    "task": {"pickup": fake_pickup,
                             "delivery": fake_delivery,
                             "task_id": 999, "peer_id": None},
                    "phase": "pickup"})
        legacy.close()
        fleet.quit()
        agent_log = "".join(f.read_text(errors="ignore")
                            for f in sorted(log_dir.glob("agent_*.log")))
        assert swap_seen, ("agent never sent a swap_request to the parked "
                           "legacy peer:\n" + agent_log[-2000:])
        assert goal_adopted, (
            "request_id-less swap_response was dropped — the agent never "
            "adopted the offered task's pickup goal:\n" + agent_log[-2000:])


def test_legacy_goal_swap_cannot_strand_agent(built, tiny_map, tmp_path):
    """Legacy-wire compat (round 5): our agents coordinate exchanges via
    swap_request (task+phase), but a FOREIGN peer speaking the
    reference's goal_swap wire can still move our agent's goal without
    its task.  The agent must answer protocol-correctly (response nested
    under a "data" STRING, the reference's wire quirk) and must NOT
    freeze parked at the foreign goal: the decision loop's resume guard
    re-targets the agent's own task, so the task still completes."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient

    log_dir = tmp_path / "logs"
    port = _free_port()
    with Fleet("decentralized", num_agents=1, port=port, map_file=tiny_map,
               log_dir=str(log_dir)) as fleet:
        time.sleep(3.5)
        legacy = BusClient(port=port, peer_id="legacy-peer")
        legacy.subscribe("mapd")
        fleet.command("tasks 1")

        # learn the agent's id and task from the bare Task broadcast
        agent_id = task_id = None
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and agent_id is None:
            f = legacy.recv(timeout=2.0)
            if not f or f.get("op") != "msg":
                continue
            d = f.get("data") or {}
            if "pickup" in d and "delivery" in d:
                agent_id, task_id = d["peer_id"], d["task_id"]
        assert agent_id, "no Task broadcast observed"
        time.sleep(1.5)  # let the agent start walking

        legacy.publish("mapd", {
            "type": "goal_swap_request",
            "request_id": "legacy-1",
            "from_peer": "legacy-peer",
            "to_peer": agent_id,
            "my_goal": [11, 11],  # far corner: a goal with no task behind it
        })

        # three observable stages, in order: the swap is answered, the
        # agent's broadcast goal actually becomes the foreign cell (the
        # displacement happened — otherwise the resume guard under test
        # is never exercised), and a task completes AFTER that (the
        # manager's closed loop keeps tasks flowing, so a frozen agent
        # would produce no further completions).
        swap_answered = goal_moved = completed_after = False
        deadline = time.monotonic() + 75
        while (time.monotonic() < deadline
               and not (swap_answered and goal_moved and completed_after)):
            f = legacy.recv(timeout=2.0)
            if not f or f.get("op") != "msg":
                continue
            d = f.get("data") or {}
            if d.get("type") == "goal_swap_response":
                inner = json.loads(d["data"])  # nested-string wire quirk
                if inner.get("to_peer") == "legacy-peer":
                    assert inner.get("accepted") is True
                    swap_answered = True
            elif (d.get("type") == "position"
                    and d.get("peer_id") == agent_id
                    and d.get("goal") == [11, 11]):
                goal_moved = True
            elif d.get("type") == "task_metric_completed" and goal_moved:
                completed_after = True
        legacy.close()
        fleet.quit()
        agent_log = "".join(f.read_text(errors="ignore")
                            for f in sorted(log_dir.glob("agent_*.log")))
        assert swap_answered, "goal_swap_request was not answered"
        assert goal_moved, (
            "agent never adopted the foreign goal — the legacy swap was "
            "silently ignored:\n" + agent_log[-2000:])
        assert completed_after, (
            "no task completed after the legacy goal displacement — the "
            "agent froze at the foreign goal:\n" + agent_log[-2000:])


def test_late_swap_response_cannot_revive_completed_task(built, tiny_map,
                                                         tmp_path):
    """ADVICE r5 race: the agent offers its task in a swap_request, then
    completes it locally before the response arrives (the blocker moved
    away).  The LATE swap_response still matches the outstanding exchange
    by request_id — without clearing pending_swap at completion the agent
    would adopt the response's task: its own finished task offered back
    (re-executing it), or a foreign task clobbering the fresh assignment
    the manager's done-refill just made.  The agent must ignore it."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient

    log_dir = tmp_path / "logs"
    port = _free_port()
    # flat JSON wire so the scripted peer sees positions at tick rate; a
    # generous swap timeout keeps the exchange outstanding across the
    # complete-then-respond window without racing the 2 s default
    with Fleet("decentralized", num_agents=1, port=port, map_file=tiny_map,
               log_dir=str(log_dir),
               env={"JG_REGION_GOSSIP": "0",
                    "MAPD_SWAP_TIMEOUT_MS": "6000"}) as fleet:
        time.sleep(3.5)
        peer = BusClient(port=port, peer_id="slow-responder")
        peer.subscribe("mapd")
        fleet.command("tasks 1")

        def next_hop(pos, goal):
            # reference neighbor order, first strict improvement — the
            # same hop the agent's BFS descent picks on an empty map
            x, y = pos
            gx, gy = goal
            d0 = abs(x - gx) + abs(y - gy)
            for dx, dy in ((0, 1), (1, 0), (0, -1), (-1, 0)):
                nx, ny = x + dx, y + dy
                if 0 <= nx < 12 and 0 <= ny < 12 \
                        and abs(nx - gx) + abs(ny - gy) < d0:
                    return [nx, ny]
            return None

        swap_req = None      # the request we deliberately answer LATE
        swap_req_at = 0.0
        offered_task = None
        task = None          # the bare Task the manager dispatched
        done_seen = False
        parked_for = None    # task id we parked for (re-arm per refill)
        deadline = time.monotonic() + 75
        while time.monotonic() < deadline:
            f = peer.recv(timeout=1.0)
            if not f or f.get("op") != "msg":
                continue
            d = f.get("data") or {}
            typ = d.get("type")
            if typ is None and "pickup" in d and "delivery" in d:
                task = d  # incl. the refill after a missed window
            elif typ == "position" and d.get("peer_id") != "slow-responder":
                pos, goal = d.get("pos"), d.get("goal")
                if swap_req is None and task is not None \
                        and parked_for != task["task_id"] \
                        and pos and goal == task["delivery"] \
                        and 3 <= (abs(pos[0] - goal[0])
                                  + abs(pos[1] - goal[1])) <= 5:
                    # 3-5 hops from the DELIVERY: park TWO hops ahead of
                    # the agent (its beacon precedes its move within the
                    # same tick, so parking on the immediate next hop
                    # lands a tick late and it walks through).  Two hops
                    # ahead, the claim is in its nearby cache before the
                    # decision that would enter the cell: Rule 3 fires a
                    # swap_request offering its task, and completion
                    # follows a few moves after we step aside — inside
                    # the swap-timeout window.
                    hop1 = next_hop(pos, goal)
                    hop2 = next_hop(hop1, goal) if hop1 else None
                    if hop2:
                        parked_for = task["task_id"]
                        peer.publish("mapd", {
                            "type": "position",
                            "peer_id": "slow-responder",
                            "pos": hop2, "goal": hop2})
            elif typ == "swap_request" \
                    and d.get("to_peer") == "slow-responder":
                swap_req = d
                swap_req_at = time.monotonic()
                offered_task = d.get("task")
                # "move away" so the agent can proceed and complete; do
                # NOT answer yet — that is the race
                peer.publish("mapd", {
                    "type": "position", "peer_id": "slow-responder",
                    "pos": [11, 0], "goal": [11, 0]})
            elif d.get("status") == "done" and swap_req is not None:
                if offered_task \
                        and d.get("task_id") == offered_task.get("task_id"):
                    if time.monotonic() - swap_req_at < 4.0:
                        done_seen = True
                        break
                    # the arm was slow enough that the swap timeout may
                    # already have cleared the exchange on its own —
                    # that wouldn't exercise the completion-clears-offer
                    # path.  Re-arm on the next task cycle instead.
                    swap_req = offered_task = None
        assert swap_req is not None, "agent never sent a swap_request"
        assert done_seen, "agent did not complete the offered task"
        # the late response: offer the agent's own completed task back,
        # echoing the request_id (the exchange it still has outstanding
        # unless completion cleared it)
        time.sleep(0.6)  # let the done_ack land (unacked_done cleared)
        peer.publish("mapd", {
            "type": "swap_response",
            "request_id": swap_req["request_id"],
            "from_peer": "slow-responder",
            "to_peer": swap_req["from_peer"],
            "task": offered_task,
            "phase": "delivery"})
        time.sleep(2.5)
        peer.close()
        fleet.quit()
        agent_log = "".join(f.read_text(errors="ignore")
                            for f in sorted(log_dir.glob("agent_*.log")))
        tid = offered_task.get("task_id")
        assert f"adopted task {tid}" not in agent_log, (
            "late swap_response revived the completed task:\n"
            + agent_log[-2500:])
        # exactly one completion of that task id (no re-execution)
        assert agent_log.count(f"Task {tid} DONE") == 1, agent_log[-2500:]


def test_region_gossip_flat_json_peer_interop(built, tiny_map, tmp_path):
    """Caps negotiation e2e (ISSUE 4): with region gossip ON (default), a
    flat-topic JSON peer that never speaks pos1 must still interoperate —
    it discovers the agent via the slow JSON beacon, and once it
    announces itself with a capsless JSON position the agent echoes JSON
    positions at full tick rate (and sees the peer in its own nearby
    cache, observable as a swap_request when the peer parks in its
    way)."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient

    log_dir = tmp_path / "logs"
    port = _free_port()
    with Fleet("decentralized", num_agents=1, port=port, map_file=tiny_map,
               log_dir=str(log_dir)) as fleet:
        time.sleep(3.5)
        legacy = BusClient(port=port, peer_id="flat-peer", fastframe=False)
        legacy.subscribe("mapd")
        fleet.command("tasks 1")

        # 1. discovery: the slow JSON beacon reaches a flat-topic peer
        discovered = False
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not discovered:
            f = legacy.recv(timeout=1.0)
            if f and f.get("op") == "msg" \
                    and (f.get("data") or {}).get("type") == "position":
                discovered = True
        assert discovered, "no JSON discovery beacon on the flat topic"

        # 2. capsless JSON position -> full-rate echo
        legacy.publish("mapd", {"type": "position", "peer_id": "flat-peer",
                                "pos": [0, 0], "goal": [0, 0]})
        n = 0
        t0 = time.monotonic()
        while time.monotonic() - t0 < 3.0:
            f = legacy.recv(timeout=0.5)
            if f and f.get("op") == "msg" \
                    and (f.get("data") or {}).get("type") == "position":
                n += 1
        assert n >= 4, (
            f"only {n} JSON positions in 3 s after legacy evidence — "
            "full-rate echo did not engage (500 ms tick should give ~6)")
        legacy.close()
        fleet.quit()


def test_manager_liveness_sweeps_held_through_outage(built, tiny_map,
                                                     tmp_path):
    """ADVICE r5: heartbeats cannot arrive while the bus is down, so a
    bus outage longer than agent_stale_ms must NOT make the manager
    re-queue live peers' tasks — the sweeps hold during the outage and
    drain one claim cycle after the reconnect, letting post-outage
    heartbeat claims land before the deliberate-duplicate re-dispatch."""
    from p2p_distributed_tswap_tpu.core.config import RuntimeConfig
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    log_dir = tmp_path / "logs"
    port = _free_port()
    cfg = RuntimeConfig(agent_stale_ms=3000, cleanup_interval_ms=1000)
    new_bus = None
    with Fleet("decentralized", num_agents=0, port=port, map_file=tiny_map,
               log_dir=str(log_dir), config=cfg) as fleet:
        try:
            p1 = BusClient(port=port, peer_id="py-live-1", reconnect=True)
            p1.subscribe("mapd")
            time.sleep(1.0)
            fleet.command("tasks 1")
            task = None
            deadline = time.monotonic() + 10
            last_beat = 0.0

            def beat():
                msg = {"type": "position_update", "peer_id": "py-live-1",
                       "position": [1, 1]}
                if task is not None:
                    msg["busy_task"] = task["task_id"]
                p1.publish("mapd", msg)

            while time.monotonic() < deadline and task is None:
                if time.monotonic() - last_beat >= 0.4:
                    last_beat = time.monotonic()
                    beat()
                f = p1.recv(timeout=0.2)
                if f and f.get("op") == "msg":
                    d = f.get("data") or {}
                    if "pickup" in d and d.get("peer_id") == "py-live-1":
                        task = d
            assert task is not None, "task never dispatched"
            for _ in range(3):  # a few busy claims land pre-outage
                beat()
                time.sleep(0.4)

            fleet.procs[0].kill()  # bus outage, LONGER than agent_stale_ms
            time.sleep(4.5)
            new_bus = subprocess.Popen(
                [str(BUILD_DIR / "mapd_bus"), str(port)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            # keep claiming through the reconnect window
            t_end = time.monotonic() + 8
            while time.monotonic() < t_end:
                beat()
                time.sleep(0.4)
                p1.recv(timeout=0.05)
            log = (log_dir / "manager.log").read_text(errors="ignore")
            p1.close()
            fleet.quit()
            assert "bus: reconnected" in log, log[-2000:]
            assert "unclaimed by any peer" not in log, (
                "sweep re-queued a live peer's task through the outage:\n"
                + log[-3000:])
            assert "silent for" not in log, (
                "silence sweep dropped a live peer through the outage:\n"
                + log[-3000:])
        finally:
            if new_bus is not None:
                new_bus.kill()


@pytest.mark.parametrize("mode", ["decentralized", "centralized"])
def test_fleet_survives_bus_restart(built, tiny_map, tmp_path, mode):
    """Kill busd mid-run and restart it on the same port: every role must
    reconnect with backoff, resubscribe, re-announce, and the fleet must
    complete NEW tasks after the outage.  The reference's brokerless
    gossipsub mesh has no hub to lose (manager.rs:94-98); this closes the
    equivalent single-point-of-failure gap of the hub design (VERDICT r2
    item 5)."""
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    log_dir = tmp_path / "logs"
    port = _free_port()
    new_bus = None
    with Fleet(mode, num_agents=2, port=port, map_file=tiny_map,
               log_dir=str(log_dir)) as fleet:
        try:
            time.sleep(4)  # discovery + initial positions
            fleet.command("tasks 2")

            def done_count():
                return sum(
                    f.read_text(errors="ignore").count("DONE")
                    for f in log_dir.glob("agent_*.log"))

            assert _wait_for(lambda: done_count() >= 1, timeout=45), (
                "fleet not functional before the outage")

            fleet.procs[0].kill()  # busd is the first spawned process
            time.sleep(1.5)        # let every role notice and start backoff
            new_bus = subprocess.Popen(
                [str(BUILD_DIR / "mapd_bus"), str(port)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

            def all_reconnected():
                logs = [f.read_text(errors="ignore")
                        for f in log_dir.glob("*.log")
                        if f.name != "bus.log"]
                return all("bus: reconnected" in t for t in logs) and logs

            assert _wait_for(all_reconnected, timeout=20), (
                "roles did not reconnect: " + "".join(
                    f.read_text(errors="ignore")[-300:]
                    for f in sorted(log_dir.glob("*.log"))))

            base = done_count()
            fleet.command("tasks 2")
            completed = _wait_for(lambda: done_count() >= base + 2,
                                  timeout=60)
            fleet.quit()
            assert completed, (
                "no task completions after bus restart: " + "".join(
                    "\n== " + f.name + " ==\n"
                    + f.read_text(errors="ignore")[-2500:]
                    for f in sorted(log_dir.glob("*.log"))))
        finally:
            if new_bus is not None:
                new_bus.kill()


@pytest.mark.parametrize("mode", ["decentralized", "centralized"])
def test_lost_done_retransmitted_and_counted_once(built, tiny_map, tmp_path,
                                                  mode):
    """Kill the bus BETWEEN an agent's done and the manager's receipt: the
    done published into the outage is dropped (the bus is lossy), which
    used to strand the manager's busy bookkeeping forever — a chatty agent
    whose done was lost never trips the silence-keyed re-queue (VERDICT r4
    weak #1).  The agent must retransmit the done until the manager acks,
    the task must be counted exactly once, and the closed task loop must
    resume.  The reference loses such tasks outright
    (decentralized/manager.rs:185-189)."""
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    log_dir = tmp_path / "logs"
    csv = tmp_path / "task_metrics.csv"
    port = _free_port()
    new_bus = None
    with Fleet(mode, num_agents=1, port=port, map_file=tiny_map,
               log_dir=str(log_dir)) as fleet:
        try:
            time.sleep(4)  # discovery + initial positions
            fleet.command("tasks 1")

            def agent_log_text():
                return "".join(f.read_text(errors="ignore")
                               for f in log_dir.glob("agent_*.log"))

            assert _wait_for(lambda: "TASK RECEIVED" in agent_log_text(),
                             timeout=15), "task not delivered"
            if mode == "centralized":
                # the centralized agent only moves on manager instructions,
                # so the outage must start when the journey is DONE but the
                # done may still be unacked; with a 2 s retry cadence the
                # ack race stays open long enough to kill the bus into it.
                # Simplest deterministic window: wait for the DONE log line
                # and kill the bus within the same tick.
                assert _wait_for(lambda: "DONE" in agent_log_text(),
                                 timeout=45), "task did not complete"
            fleet.procs[0].kill()  # bus down: the done (or its ack) drops
            if mode == "decentralized":
                # the decentralized agent moves on its own local decisions,
                # so it completes the journey DURING the outage and the
                # done publish is dropped with certainty
                assert _wait_for(lambda: "DONE" in agent_log_text(),
                                 timeout=45), (
                    "agent did not complete during the outage: "
                    + agent_log_text()[-500:])
            time.sleep(1.0)
            new_bus = subprocess.Popen(
                [str(BUILD_DIR / "mapd_bus"), str(port)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

            def counted_once():
                fleet.command(f"save {csv}")
                time.sleep(0.5)
                if not csv.exists():
                    return False
                rows = [r for r in csv.read_text().splitlines()[1:]
                        if r.split(",")[0] == "1"]
                return (len(rows) == 1 and rows[0].endswith(",completed"))

            assert _wait_for(counted_once, timeout=30, interval=2), (
                "task 1 not counted exactly once after the outage:\n"
                + (csv.read_text() if csv.exists() else "<no csv>")
                + (log_dir / "manager.log").read_text(errors="ignore")[-800:])
            if mode == "decentralized":
                # the done was published into the outage with certainty, so
                # the heal must have gone through the retransmit path
                assert "retransmitting done" in agent_log_text(), (
                    agent_log_text()[-800:])
            # the closed loop resumed: the manager refilled with a new task
            mgr_log = log_dir / "manager.log"
            assert _wait_for(
                lambda: mgr_log.read_text(errors="ignore").count("📤") >= 2,
                timeout=15), "closed task loop did not resume"
            fleet.quit()
        finally:
            if new_bus is not None:
                new_bus.kill()


def test_fleet_metrics_beacons_and_fleet_top(built, tiny_map, tmp_path):
    """ISSUE 2 acceptance: with a fleet running (busd + centralized manager
    + solverd + agents), every process beacons its live-metrics registry on
    bus topic ``mapd.metrics`` and ``fleet_top --once --json`` returns a
    rollup with >= 2 peers carrying tick/bandwidth/cache fields."""
    import sys

    log_dir = tmp_path / "logs"
    port = _free_port()
    with Fleet("centralized", num_agents=2, port=port, map_file=tiny_map,
               solver="tpu", log_dir=str(log_dir),
               solverd_args=["--cpu"]) as fleet:
        time.sleep(4)  # discovery + initial positions
        fleet.command("tasks 2")
        time.sleep(4)  # let planning ticks + a beacon interval elapse
        top = subprocess.run(
            [sys.executable, "analysis/fleet_top.py", "--port", str(port),
             "--once", "--json", "--wait", "6"],
            capture_output=True, text=True, timeout=120,
            cwd=str(Path(__file__).resolve().parents[1]))
        fleet.quit()
    assert top.returncode == 0, top.stderr + top.stdout
    rollup = json.loads(top.stdout)
    peers = rollup["peers"]
    assert rollup["fleet"]["peers"] >= 2, rollup
    by_proc = {p["proc"]: p for p in peers.values()}
    # the hub, the manager, and the solver daemon all appear in one rollup
    # (C++ registry mirror and Python registry publish the same schema)
    assert "busd" in by_proc, sorted(by_proc)
    assert "manager_centralized" in by_proc, sorted(by_proc)
    assert "solverd" in by_proc, sorted(by_proc)
    for proc in ("busd", "manager_centralized", "solverd"):
        assert by_proc[proc]["stale"] is False, by_proc[proc]
    # per-peer tick percentiles vs the 500 ms budget, from live histograms
    mgr = by_proc["manager_centralized"]
    assert mgr["tick"] and mgr["tick"]["p95_ms"] is not None, mgr
    assert mgr["tick"]["budget_ms"] == 500.0
    sd = by_proc["solverd"]
    assert sd["tick"] and sd["tick"]["p95_ms"] is not None, sd
    # wire-byte bandwidth (the corrected framed counts) and cache rates
    assert sd["bandwidth"]["bytes_sent"] > 0
    assert mgr["bandwidth"]["bytes_sent"] > 0
    assert sd["cache"] is not None and 0 <= sd["cache"]["hit_rate"] <= 1, sd


def test_python_bus_client_reconnects(built):
    """The Python BusClient (solverd's transport) must also survive a busd
    restart: resubscribe and resume delivery (VERDICT r2 item 5)."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR

    port = _free_port()
    bus = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL)
    bus2 = None
    reconnects = []
    try:
        time.sleep(0.3)
        sub = BusClient(port=port, peer_id="sub", reconnect=True,
                        on_reconnect=lambda: reconnects.append(1))
        pub = BusClient(port=port, peer_id="pub", reconnect=True)
        sub.subscribe("t")
        time.sleep(0.2)

        def next_msg(client, timeout):
            # skip non-msg frames (welcome handshake, peer events)
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                f = client.recv(
                    timeout=max(0.05, deadline - time.monotonic()))
                if f and f.get("op") == "msg":
                    return f
            return None

        pub.publish("t", {"x": 1})
        frame = next_msg(sub, 3.0)
        assert frame and frame["data"]["x"] == 1

        bus.kill()
        bus.wait()
        time.sleep(0.6)  # let both clients notice the outage
        assert sub.recv(timeout=0.3) is None  # outage reads as timeout
        bus2 = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        # publish until the resubscribed client sees a frame again
        got = None
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and got is None:
            pub.publish("t", {"x": 2})
            f = next_msg(sub, 0.5)
            if f and f["data"].get("x") == 2:
                got = f
        assert got, "no delivery after busd restart"
        assert reconnects, "on_reconnect callback did not fire"
        sub.close()
        pub.close()
    finally:
        for p in (bus, bus2):
            if p is not None and p.poll() is None:
                p.kill()


# ---------------------------------------------------------------------------
# ISSUE 5: distributed task-causality tracing + flight recorder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["decentralized", "centralized"])
def test_task_timeline_reconstructs_e2e(built, tiny_map, tmp_path, mode):
    """ISSUE 5 tentpole acceptance: with tracing on, a live fleet's
    completed tasks reconstruct into GAP-FREE causal timelines — every
    lifecycle hop present (dispatch -> claim -> pickup -> delivery ->
    done -> done-ack), no orphan events, monotone hop counters, and the
    attributed phases summing to the end-to-end latency within the
    clock-skew clamp — in both runtime modes."""
    log_dir = tmp_path / "logs"
    trace_dir = tmp_path / "trace"
    env = {"JG_TRACE": "1", "JG_TRACE_DIR": str(trace_dir),
           "JG_TRACE_SAMPLE": "1.0"}
    with Fleet(mode, num_agents=2, port=_free_port(), map_file=tiny_map,
               log_dir=str(log_dir), env=env) as fleet:
        time.sleep(4)
        fleet.command("tasks 2")

        def agents_done():
            return sum(f.read_text(errors="ignore").count("DONE")
                       for f in log_dir.glob("agent_*.log")) >= 2

        completed = _wait_for(agents_done, timeout=60)
        time.sleep(2)  # done-acks and their events settle
        fleet.quit()
        assert completed, "".join(
            f.read_text(errors="ignore")[-500:]
            for f in sorted(log_dir.glob("*.log")))

    tl = _load_analysis("task_timeline")
    summary = tl.summarize(trace_dir)
    assert summary["tasks_done"] >= 2, summary
    assert summary["coverage"] is not None \
        and summary["coverage"] >= 0.95, summary
    assert summary["orphans"] == 0, summary["orphan_trace_ids"]
    assert summary["hop_violations"] == 0, summary
    complete = [r for r in summary["tasks"] if r["complete"]]
    assert complete
    for r in complete:
        # phases telescope from task.queue to task.done_ack; clamped
        # negative segments are reported as skew, so the identity is
        # sum(phases) == queue_to_ack + skew (within rounding)
        total = sum(r["phases_ms"].values())
        assert total == pytest.approx(
            r["queue_to_ack_ms"] + r["skew_ms"], abs=2.0), r
        # cross-process coverage: at least one manager and one agent
        # contributed events to the timeline
        assert any(p.startswith("manager") for p in r["procs"]), r
        assert any(p.startswith("agent") for p in r["procs"]), r


def test_flight_dump_over_bus(built, tiny_map, tmp_path):
    """Flight recorder e2e: a bus `flight_dump` request makes every
    fleet process dump its always-on event ring to the log dir (no
    tracing enabled — the black box must work cold), and blackbox.py
    renders the merged view."""
    from p2p_distributed_tswap_tpu.runtime.bus_client import BusClient

    log_dir = tmp_path / "logs"
    port = _free_port()
    with Fleet("centralized", num_agents=2, port=port, map_file=tiny_map,
               log_dir=str(log_dir)) as fleet:
        spy = BusClient(port=port, peer_id="flight-spy")
        spy.subscribe("mapd")
        time.sleep(4)
        fleet.command("tasks 2")
        time.sleep(2)  # some lifecycle churn for the rings
        spy.publish("mapd", {"type": "flight_dump"})

        responders = set()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and len(responders) < 3:
            f = spy.recv(timeout=1.0)
            if f and f.get("op") == "msg":
                d = f.get("data") or {}
                if d.get("type") == "flight_dump_response":
                    responders.add(d.get("peer_id") or d.get("proc"))
        spy.close()
        fleet.quit()
    # manager + both agents answered (busd has no client-side handler)
    assert len(responders) >= 3, responders
    dumps = list(log_dir.glob("*.flight.jsonl"))
    assert len(dumps) >= 3, dumps
    bb = _load_analysis("blackbox")
    metas, events = bb.load_dumps(log_dir)
    assert metas and events
    # the dispatched tasks left their lifecycle in the rings
    assert any(str(e.get("event", "")).startswith("task.")
               for e in events), events[:10]
