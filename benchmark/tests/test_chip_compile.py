"""Compile the start cell's programs for a described TPU v5e (no chip):
the prime, the donated step, the snapshot copy, and show that the
working state, its snapshot and the step's temporaries fit one chip.

The topology is described inside a module fixture, never at import
(only one process at a time may load libtpu).  Nothing runs here."""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import BENCH

HBM_BYTES = 15.75e9  # what the v5e compiler reports as usable HBM


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    from p2p_distributed_tswap_tpu.ops import sweep_pallas

    jax.clear_caches()
    monkeypatch.setattr(sweep_pallas, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def test_start_programs_fit_one_chip(one_chip, on_tpu):
    from p2p_distributed_tswap_tpu.core.config import SolverConfig
    from p2p_distributed_tswap_tpu.solver import mapd

    conf = json.loads((BENCH / "configs" / "lorr-warehouse-10k.json").read_text())
    m, n = conf["map"], conf["agents"]
    cfg = SolverConfig(height=m["height"], width=m["width"], num_agents=n,
                       **conf["solver"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((n,), jnp.int32), sds((conf["orders"], 2), jnp.int32),
            sds((m["height"], m["width"]), jnp.bool_))
    prep = jax.jit(mapd.prepare_state, static_argnums=0).lower(cfg, *args)
    prep = prep.compile()
    state, tasks = jax.eval_shape(lambda *a: mapd.prepare_state(cfg, *a),
                                  *args)
    state = jax.tree.map(lambda x: sds(x.shape, x.dtype), state)
    tasks = sds(tasks.shape, tasks.dtype)
    step = jax.jit(mapd.mapd_step, static_argnums=0, donate_argnums=1).lower(
        cfg, state, tasks, args[2]).compile()
    # 500 columns are not lane-aligned: the XLA sweep, no Pallas call
    assert "tpu_custom_call" not in step.as_text()
    copy = jax.jit(lambda x: jax.tree.map(jnp.copy, x)).lower(
        state).compile()
    state_b = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    sm, pm, cm = (c.memory_analysis() for c in (step, prep, copy))
    # resident while a tick runs: the working state (donated in, aliased
    # out), the snapshot, the inputs, and the step's temporaries
    tick_b = 2 * state_b + tasks.size * 4 + args[2].size + sm.temp_size_in_bytes
    print(f"\nstart cell state {state_b} B, step temp {sm.temp_size_in_bytes} B, "
          f"step alias {sm.alias_size_in_bytes} B, prime temp "
          f"{pm.temp_size_in_bytes} B, copy temp {cm.temp_size_in_bytes} B, "
          f"resident while ticking {tick_b} B")
    assert sm.alias_size_in_bytes >= 0.99 * state_b   # donation took
    assert tick_b < HBM_BYTES
    assert state_b + pm.temp_size_in_bytes + pm.output_size_in_bytes \
        < HBM_BYTES
