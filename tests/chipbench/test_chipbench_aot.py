"""The priority kernel compiled ahead of time for a described v5e chip at the
benchmark's two capacities, so that a change which grows its use of fast
memory fails here and not in a check on the chip.  Nothing runs: a compile
says nothing about results or times."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import harness


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """An ahead-of-time TPU program cannot be read back without a chip: keep
    it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("config", ["walker_r2d2", "cheetah_pixels"])
def test_priority_kernel_compiles_for_v5e_at_the_cells_capacity(
    config, one_chip, no_compile_cache
):
    from r2d2dpg_tpu.ops.pallas.scatter import _pallas_scatter

    cfg = harness.load_json("configs", config)
    capacity, batch = cfg["capacity"], cfg["batch_size"]
    avals = (
        jax.ShapeDtypeStruct((capacity,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=one_chip),
    )
    compiled = _pallas_scatter.trace(*avals).lower(
        lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()
