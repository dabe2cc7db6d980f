"""Compile the qsnap kernels for a described TPU v5e chip (no chip needed).

Interpret mode runs a kernel body on the CPU but never checks it against
the chip's tiling rules; the TPU compiler does. These cases compile the
main path's kernels at repro-100m's real leaf sizes, plus the awkward row
counts that once produced an unaligned block. Nothing runs, so they say
nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.qsnap import QSNAP_BLOCK, qsnap_dequantize, qsnap_quantize

# repro-100m's embedding leaf: vocab 32768 x d_model 768
EMBED_ELEMS = 32768 * 768


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("n_elems", [EMBED_ELEMS, 300 * QSNAP_BLOCK,
                                     257 * QSNAP_BLOCK],
                         ids=["embed_98304_rows", "rows_300", "rows_257"])
def test_qsnap_quantize_compiles(one_chip, no_persistent_cache, n_elems):
    x = jax.ShapeDtypeStruct((n_elems,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(qsnap_quantize).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qsnap_dequantize_compiles(one_chip, no_persistent_cache):
    rows = 2304
    codes = jax.ShapeDtypeStruct((rows * QSNAP_BLOCK,), jnp.int8,
                                 sharding=one_chip)
    scales = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(qsnap_dequantize).lower(codes, scales).compile()
    assert "tpu_custom_call" in compiled.as_text()
