"""Compile the decentralized cell's programs for a described TPU v5e (no
chip) and show that they hold what the centralized cell holds: the same
working state, and step temporaries within a few MB of
``lorr10k.lifelong``'s (350,460,004 B and 57,786,880 B, the state and
step temporaries ``test_chip_compile.py`` reads for it).

The topology is described inside a module fixture, never at import
(only one process at a time may load libtpu).  Nothing runs here."""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import BENCH

HBM_BYTES = 15.75e9        # what the v5e compiler reports as usable HBM
LORR_STATE_B = 350_460_004
LORR_STEP_TEMP_B = 57_786_880
FEW_MB = 4 << 20


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


def test_decent_programs_fit_like_the_centralized_cell(one_chip):
    from p2p_distributed_tswap_tpu.core.config import SolverConfig
    from p2p_distributed_tswap_tpu.solver import mapd

    conf = json.loads((BENCH / "configs" /
                       "lorr-warehouse-10k-decent.json").read_text())
    m, n = conf["map"], conf["agents"]
    cfg = SolverConfig(height=m["height"], width=m["width"], num_agents=n,
                       **conf["solver"])
    assert cfg.stale_mode

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((n,), jnp.int32), sds((conf["orders"], 2), jnp.int32),
            sds((m["height"], m["width"]), jnp.bool_))
    prep = jax.jit(mapd.prepare_state, static_argnums=0).lower(
        cfg, *args).compile()
    state, tasks = jax.eval_shape(lambda *a: mapd.prepare_state(cfg, *a),
                                  *args)
    state = jax.tree.map(lambda x: sds(x.shape, x.dtype), state)
    tasks = sds(tasks.shape, tasks.dtype)
    step = jax.jit(mapd.mapd_step, static_argnums=0, donate_argnums=1).lower(
        cfg, state, tasks, args[2]).compile()
    assert "tpu_custom_call" not in step.as_text()     # the XLA sweep
    state_b = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    sm, pm = step.memory_analysis(), prep.memory_analysis()
    tick_b = 2 * state_b + tasks.size * 4 + args[2].size + sm.temp_size_in_bytes
    print(f"\ndecent cell state {state_b} B, step temp "
          f"{sm.temp_size_in_bytes} B, step code "
          f"{sm.generated_code_size_in_bytes} B, prime temp "
          f"{pm.temp_size_in_bytes} B, resident while ticking {tick_b} B")
    assert state_b == LORR_STATE_B
    assert abs(sm.temp_size_in_bytes - LORR_STEP_TEMP_B) < FEW_MB
    assert sm.alias_size_in_bytes >= 0.99 * state_b   # donation took
    assert tick_b < HBM_BYTES
