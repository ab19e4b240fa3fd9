"""Compile the main path for a described TPU v5e — no chip needed.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (jax.experimental.topologies).  Each test lowers
one main-path program at its real size and asserts that the compiler
accepts it, that the Pallas sweep is in it (``tpu_custom_call``) and that
it fits one chip's HBM.  Nothing runs: these are compile guards, never
timings.

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and every xdist worker imports
every test file.  Never add a program here that still aborts the
compiler process (the multi-field ``field_fused`` kernel does): the
abort would take the whole xdist worker with it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from p2p_distributed_tswap_tpu.core.config import SolverConfig
from p2p_distributed_tswap_tpu.models import scenarios
from p2p_distributed_tswap_tpu.ops import sweep_pallas
from p2p_distributed_tswap_tpu.parallel import sharded2d
from p2p_distributed_tswap_tpu.parallel.mesh import AGENTS_AXIS, TILES_AXIS
from p2p_distributed_tswap_tpu.solver import mapd

HBM_BYTES = 15.75e9  # what the v5e compiler reports as usable HBM


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the sweep dispatch onto the Pallas path while tracing for
    the described chip (this process's backend is the CPU), and drop
    every trace made that way so no later CPU test reuses one."""
    jax.clear_caches()
    monkeypatch.setattr(sweep_pallas, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _check(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("side,r", [(1024, 64), (4096, 4)])
@pytest.mark.parametrize("axis", [1, 2])
def test_sweep_compiles(topo, side, r, axis):
    one = SingleDeviceSharding(topo.devices[0])
    d = jax.ShapeDtypeStruct((r, side, side), jnp.int32, sharding=one)
    free = jax.ShapeDtypeStruct((side, side), jnp.bool_, sharding=one)
    fn = jax.jit(lambda d, f: sweep_pallas.sweep(d, f, axis, reverse=False))
    compiled = fn.lower(d, free).compile()
    _check(compiled)
    # the kernel's name is the custom call's name, which a trace shows
    assert "%sweep8_rows" in compiled.as_text()


def test_flagship_solve_compiles(topo, on_tpu):
    """The one-program flagship solve (bench.py's full-solve rung,
    chip_smoke.py's first phase): 10k agents on the 1024^2 warehouse."""
    scn = scenarios.FLAGSHIP
    cfg = SolverConfig(height=1024, width=1024, num_agents=scn.num_agents,
                       replan_chunk=scn.replan_chunk)
    one = SingleDeviceSharding(topo.devices[0])
    args = (jax.ShapeDtypeStruct((scn.num_agents,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((scn.num_tasks, 2), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((1024, 1024), jnp.bool_, sharding=one))
    run = jax.jit(mapd.run_mapd, static_argnums=0)
    _check(run.lower(cfg, *args).compile())


def test_sharded2d_solve_compiles(topo, on_tpu):
    """The 2x2 (agents x tiles) solve of ``chip_smoke.py --chips 4``:
    2048 agents on the 1024^2 warehouse; each device sweeps its
    lane-aligned 512x1024 band with the Pallas kernel."""
    n, side = 2048, 1024
    cfg = SolverConfig(height=side, width=side, num_agents=n,
                       max_timesteps=3000, replan_chunk=64)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                (AGENTS_AXIS, TILES_AXIS))
    rep = NamedSharding(mesh, P())
    run = sharded2d.make_sharded2d_runner(cfg, mesh)
    compiled = run.lower(
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((n, 2), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((side, side), jnp.bool_, sharding=rep)).compile()
    _check(compiled)
