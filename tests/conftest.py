"""Test harness: an 8-device virtual CPU mesh for the whole suite.

The suite runs on the CPU (the driver sets ``JAX_PLATFORMS=cpu``).
Sharding tests run on ``xla_force_host_platform_device_count=8`` virtual
CPU devices, which exercise the same SPMD partitioner and collectives as a
real mesh.  The CPU client is created lazily, so setting ``XLA_FLAGS``
before the first ``jax.devices("cpu")`` call still yields 8 devices, and
``jax_default_device`` keeps every test on the CPU.
``parallel.mesh.agent_mesh`` follows the default device's platform, so
sharded tests pick up the 8-device CPU mesh automatically.

The bootstrap logic is shared with __graft_entry__.dryrun_multichip via
``parallel.virtual_mesh`` (which imports no jax at module level).  Chip
compiles live in tests/test_chip_compile.py, which describes the v5e
inside a fixture and never touches the TPU library at import.
"""

from p2p_distributed_tswap_tpu.parallel.virtual_mesh import (  # noqa: E402
    force_virtual_cpu_devices)

force_virtual_cpu_devices(8)

import jax  # noqa: E402  (after XLA_FLAGS, intentionally)

jax.config.update("jax_default_device", jax.devices("cpu")[0])

import pytest  # noqa: E402


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Expose each phase's report on the item so fixtures can see whether
    the test body failed (the e2e failure-artifact collector in
    test_runtime_e2e.py dumps flight rings + log tails on rep_call.failed,
    ISSUE 5 satellite)."""
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)
