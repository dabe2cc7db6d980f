"""Compile main-path programs for a described TPU v5e chip (no chip needed).

Interpret mode runs a kernel body on the CPU but never checks it against
the chip's tiling rules; the TPU compiler does. These cases compile the
main path's kernels at repro-100m's real leaf sizes, plus the awkward row
counts that once produced an unaligned block, and the serving decode step,
whose compiled buffers show whether it copies the KV cache. Nothing runs,
so they say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.qsnap import QSNAP_BLOCK, qsnap_dequantize, qsnap_quantize
from repro.models import build_model

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


def _gqa(head_dim: int):
    """A small dense GQA model: 4 layers, 8 KV heads."""
    cfg = dataclasses.replace(
        get_config("internlm2-1.8b"), n_layers=4, d_model=1024, n_heads=16,
        n_kv_heads=8, head_dim=head_dim, d_ff=2048, vocab_size=1024)
    return cfg, build_model(cfg)


def _assert_cache_in_place(compiled, cache, n_layers: int) -> None:
    """The donated cache outputs alias their inputs, and the scratch stays
    under one layer's K+V (all sizes per device)."""
    mem = compiled.memory_analysis()
    cache_bytes = sum(
        math.prod(s.sharding.shard_shape(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(cache))
    layer_kv_bytes = cache_bytes // n_layers
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < layer_kv_bytes, (
        mem.temp_size_in_bytes, layer_kv_bytes)


def _decode_on_chip(model, sharding, batch: int, cache_len: int):
    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    cache = on_chip(model.abstract_cache(batch, cache_len))
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        on_chip(model.abstract_params()), cache,
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)).compile()
    return compiled, cache


@pytest.mark.parametrize("head_dim", [128, 64])
def test_decode_updates_the_donated_cache_in_place(one_chip,
                                                   no_persistent_cache,
                                                   head_dim):
    """The decode step as ``Engine`` jits it (cache donated) writes the
    token's row into the cache buffers it was given and holds no copy of
    the cache: its scratch stays under one layer's K+V. A 64-wide
    head_dim is stored with positions minor, a 128-wide one row-major;
    the decode keeps either layout."""
    cfg, model = _gqa(head_dim)
    compiled, cache = _decode_on_chip(model, one_chip, 8, 1024)
    _assert_cache_in_place(compiled, cache, cfg.n_layers)


def test_sharded_decode_updates_the_donated_cache_in_place(
        no_persistent_cache):
    """The dry-run's decode cell on a 16-chip tensor-parallel slice: 8 KV
    heads do not divide 16, so each chip holds an 8-wide slice of
    head_dim. The step still writes its shard of the cache in place."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.sharding.specs import (activation_sharding, make_axes,
                                      param_specs)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:4x4")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:4x4 topology can be described here: {e}")
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 16), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    axes = make_axes(mesh)
    cfg, model = _gqa(head_dim=128)
    batch, cache_len = 8, 4096

    def sharded(tree, dims):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
            tree, param_specs(dims, tree, axes))

    cache = sharded(model.abstract_cache(batch, cache_len),
                    model.cache_dims())
    replicated = NamedSharding(mesh, P())

    def step(params, cache, token, pos):
        with activation_sharding(axes):
            return model.decode_step(params, cache, token, pos)

    with mesh:
        compiled = jax.jit(
            step, donate_argnums=(1,),
            out_shardings=(None, jax.tree.map(lambda s: s.sharding, cache)),
        ).lower(sharded(model.abstract_params(), model.param_dims()), cache,
                jax.ShapeDtypeStruct((batch, 1), jnp.int32,
                                     sharding=replicated),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
                ).compile()
    _assert_cache_in_place(compiled, cache, cfg.n_layers)


def test_hybrid_decode_updates_both_kinds_of_state_in_place(
        one_chip, no_persistent_cache):
    """A Jamba period (13 Mamba layers, one attention layer with a single
    KV head) at 8 rows x 16,384 positions: the decode writes its K/V row
    and every Mamba layer's state into the donated buffers and holds no
    copy of the KV cache or of the recurrent state. A row-major pin of
    the 1-wide heads axis relaid the whole K and V in and out (76.6 MB
    of scratch)."""
    base = get_config("jamba2-3b")
    cfg = dataclasses.replace(
        base, n_layers=14, d_model=512, n_heads=4, d_ff=1024,
        vocab_size=1024, ssm=dataclasses.replace(base.ssm, dt_rank=None))
    compiled, cache = _decode_on_chip(build_model(cfg), one_chip, 8, 16384)
    _assert_cache_in_place(compiled, cache, cfg.n_layers)
