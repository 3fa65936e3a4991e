"""v5e compiles of the main-path Pallas kernels at real shapes.

Nothing runs here: each test compiles a kernel for a described (not
attached) TPU v5e chip, so what the chip's compiler would refuse — tile
alignment, memory spaces, block shapes — fails here on the CPU.  Shapes
are the paper's products configuration: 2.5M nodes, 124M edges, 1,000
seeds per worker, fanouts 15/10/5 (the bottom level's frontier is
176,000 seeds at fanout 5), 100 features.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_sample import fused_sample
from repro.kernels.gather import gather_rows

NODES, EDGES = 2_500_000, 124_000_000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("seeds,fanout", [(1_000, 15), (176_000, 5)])
def test_fused_sample_compiles_for_v5e(one_chip, seeds, fanout):
    fn = functools.partial(fused_sample, fanout=fanout, interpret=False)
    compiled = jax.jit(fn).lower(
        _shape((NODES + 1,), jnp.int32, one_chip),
        _shape((EDGES,), jnp.int32, one_chip),
        _shape((seeds,), jnp.int32, one_chip),
        _shape((), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gather_rows_compiles_for_v5e(one_chip):
    fn = functools.partial(gather_rows, interpret=False)
    compiled = jax.jit(fn).lower(
        _shape((262_144, 100), jnp.float32, one_chip),
        _shape((1_056_000,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
