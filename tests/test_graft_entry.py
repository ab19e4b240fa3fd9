"""Regression tests for the driver entry points (__graft_entry__.py).

``dryrun_multichip`` is the round's multi-chip gate: the driver calls it in a
fresh process with NO ``XLA_FLAGS`` preset and possibly a broken accelerator
plugin registered, so the function must force the virtual CPU mesh itself and
never touch the default backend.  These tests reproduce that invocation shape
in subprocesses (round-1 failure mode: MULTICHIP_r01.json ok:false — the
dryrun ran a jnp op on a libtpu-mismatched default backend).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the driver does not preset the virtual mesh
    return env


@pytest.mark.parametrize("preimport_jax", [False, True])
def test_dryrun_multichip_subprocess(preimport_jax):
    prelude = "import jax; " if preimport_jax else ""
    code = (prelude +
            "from __graft_entry__ import dryrun_multichip; "
            "dryrun_multichip(8)")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "dryrun_multichip OK" in out.stdout


def test_entry_compiles_on_forced_cpu():
    """entry() must stay jittable; check on the CPU backend, pinned via
    pin_cpu_backend so the child never reaches for an accelerator."""
    code = (
        "from p2p_distributed_tswap_tpu.parallel.virtual_mesh "
        "import pin_cpu_backend; "
        "pin_cpu_backend(1); "
        "import jax; "
        "from __graft_entry__ import entry; "
        "fn, args = entry(); "
        "out = jax.jit(fn)(*args); jax.block_until_ready(out); "
        "print('entry OK')")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "entry OK" in out.stdout
