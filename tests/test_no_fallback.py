"""No path hides the device (ISSUE 21).

A "tpu" fleet fails when its solverd does not come up, a solverd whose
backend fails exits instead of planning on the CPU, one chip never gets
one solverd per region, chip_smoke.py and bench.py fail rather than fall
back, and the compile cache sits where the caller says.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

needs_cpp = pytest.mark.skipif(
    shutil.which("cmake") is None or shutil.which("ninja") is None,
    reason="C++ toolchain unavailable")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env(**extra) -> dict:
    env = dict(os.environ)
    env.update(extra)
    return env


@needs_cpp
def test_fleet_fails_when_solverd_never_comes_up(tmp_path):
    from p2p_distributed_tswap_tpu.runtime.fleet import Fleet

    with pytest.raises(RuntimeError, match="never came up"):
        # a malformed mesh spec makes solverd exit 2 at start-up
        Fleet("centralized", num_agents=1, port=_free_port(),
              solver="tpu", log_dir=str(tmp_path),
              solverd_args=["--cpu", "--mesh", "bogus"])


def test_fleet_refuses_one_solverd_per_region_on_a_chip(tmp_path):
    from p2p_distributed_tswap_tpu.runtime.fleet import Fleet

    with pytest.raises(RuntimeError, match="multi-tenant"):
        Fleet("centralized", num_agents=1, port=_free_port(),
              solver="tpu", log_dir=str(tmp_path), regions="2x1")
    assert not any(tmp_path.iterdir())  # nothing was spawned


@pytest.mark.parametrize("regions,cpu,refused", [
    (2, False, True), (2, True, False), (1, False, False)])
def test_one_solverd_per_chip(regions, cpu, refused):
    """The rule Fleet and fleetsim both apply before spawning solverds."""
    from p2p_distributed_tswap_tpu.runtime.fleet import one_solverd_per_chip

    if refused:
        with pytest.raises(RuntimeError, match="multi-tenant"):
            one_solverd_per_chip(regions, cpu)
    else:
        one_solverd_per_chip(regions, cpu)


@needs_cpp
@pytest.mark.parametrize("platforms", ["nosuch", None])
def test_solverd_exits_when_its_backend_fails(tmp_path, platforms):
    """No CPU fallback: a backend that cannot start ends the daemon.  With
    JAX_PLATFORMS named, JAX raises; with it unset (None), JAX quietly
    comes up on the CPU here, and solverd must refuse that without
    --cpu."""
    from p2p_distributed_tswap_tpu.runtime.fleet import BUILD_DIR, ensure_built

    ensure_built()
    env = _env()
    env.pop("JAX_PLATFORMS", None)
    if platforms is not None:
        env["JAX_PLATFORMS"] = platforms
    port = _free_port()
    bus = subprocess.Popen([str(BUILD_DIR / "mapd_bus"), str(port)],
                           stdout=subprocess.DEVNULL)
    try:
        time.sleep(0.3)
        sd = subprocess.run(
            [sys.executable, "-m", "p2p_distributed_tswap_tpu.runtime.solverd",
             "--port", str(port)],
            capture_output=True, text=True, timeout=120, cwd=str(ROOT),
            env=env)
    finally:
        bus.kill()
        bus.wait()
    assert sd.returncode != 0
    assert "solverd up" not in sd.stdout


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(tmp_path, env_dir):
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c",
         "from p2p_distributed_tswap_tpu import compile_cache; "
         "print(compile_cache.enable())"],
        capture_output=True, text=True, timeout=120, cwd=str(ROOT), env=env)
    assert out.returncode == 0, out.stderr
    want = tmp_path if env_dir else ROOT / ".jax_cache"
    assert out.stdout.strip().splitlines()[-1] == str(want)


def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, timeout=300,
                         cwd=str(ROOT), env=_env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "no TPU found" in out.stdout
    assert '"ok": true' not in out.stdout + out.stderr


def test_chip_smoke_needs_the_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, timeout=60,
                         cwd=str(tmp_path))
    assert out.returncode == 2
    assert '"ok": true' not in out.stdout


def test_bench_failed_rung_exits_nonzero():
    out = subprocess.run([sys.executable, "bench.py"], capture_output=True,
                         text=True, timeout=300, cwd=str(ROOT),
                         env=_env(BENCH_RUNGS="nosuch", BENCH_SEEDS="0"))
    assert out.returncode == 1
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["value"] is None and "KeyError" in rec["error"]


def test_one_device_description():
    """bench's rung records and solverd (and chip_smoke's phases) name
    the device through one helper, with one spelling."""
    import bench
    from p2p_distributed_tswap_tpu.device import device_info
    from p2p_distributed_tswap_tpu.runtime import solverd

    assert bench.device_info is device_info is solverd.device_info
    dev = device_info()
    assert set(dev) == {"platform", "kind", "count"}
    assert dev["platform"] == "cpu" and dev["kind"] and dev["count"] >= 1


def test_bench_audit_digest_runs_in_a_child():
    """The audit axis's digest microbench runs off bench's parent."""
    out = subprocess.run([sys.executable, "bench.py", "--audit-digest"],
                         capture_output=True, text=True, timeout=300,
                         cwd=str(ROOT), env=_env(JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["device"]["platform"] == "cpu"
    assert rec["flat_us_per_beacon"] > 0 and rec["slab8_us_per_beacon"] > 0
