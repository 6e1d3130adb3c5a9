"""The grouped-heads decode kernel compiled by Mosaic for a DESCRIBED v5e
at SmallThinker's real widths, here, without a chip: what interpret mode
cannot refuse (a slice off the tiling, too much VMEM) fails this at no chip
time. Nothing runs, so it says nothing of results or times. The topology is
described inside a fixture (only the worker that is given this file loads
the TPU's library) and the tests skip where it cannot be."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_paged_attention as ppa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:  # no TPU compiler here, or another holds it
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("window,pages,columns", [(None, 24576, 640),
                                                  (4096, 12416, 259)])
def test_the_kernel_compiles_for_a_v5e_at_the_cells_widths(one_chip, window,
                                                           pages, columns):
    from jax.experimental.compilation_cache import compilation_cache

    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()   # an entry could not be read back here
    try:
        compiled = jax.jit(
            lambda q, k, v, t, p: ppa.paged_gqa_read(q, k, v, t, p, window,
                                                     interpret=False)
        ).lower(shape((48, 28, 1, 128), jnp.bfloat16),
                shape((pages + 1, 16, 512), jnp.bfloat16),
                shape((pages + 1, 16, 512), jnp.bfloat16),
                shape((48, columns), jnp.int32),
                shape((48,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    # nothing history-sized beside the pools: the kernel's scratch is VMEM
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
