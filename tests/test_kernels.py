"""Pallas kernel validation: shape/dtype sweeps vs the jnp oracles,
executed in interpret mode on CPU (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                        # bare env: seeded fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.ckpt import compression
from repro.kernels import ops, qsnap, ref

KEY = jax.random.PRNGKey(7)


def _qkv(B, S, H, Hkv, hd, dtype, T=None):
    ks = jax.random.split(KEY, 3)
    T = T or S
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, hd), jnp.float32).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # (B, S, H, Hkv, hd, window, block)
    (2, 128, 4, 2, 64, None, 64),
    (1, 256, 8, 8, 128, None, 128),
    (2, 192, 4, 2, 64, 64, 64),       # sliding window + non-pow2 seq
    (1, 128, 6, 2, 96, None, 64),     # GQA g=3, odd head_dim
    (1, 96, 4, 1, 128, 32, 32),       # MQA + window, padding path
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_oracle(case, dtype):
    B, S, H, Hkv, hd, window, blk = case
    q, k, v = _qkv(B, S, H, Hkv, hd, dtype)
    out_ref = ops.flash_attention(q, k, v, causal=True, window=window,
                                  impl="ref")
    out_pal = ops.flash_attention(q, k, v, causal=True, window=window,
                                  impl="pallas", interpret=True,
                                  block_q=blk, block_k=blk)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_pal, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


DECODE_CASES = [
    (2, 512, 8, 2, 64, 300, 128),
    (1, 1024, 4, 4, 128, 1023, 256),
    (3, 256, 8, 4, 96, 0, 128),       # pos=0: single visible slot
    (1, 640, 16, 2, 128, 400, 128),   # g=8
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_vs_oracle(case, dtype):
    B, T, H, Hkv, hd, pos, blk = case
    q, k, v = _qkv(B, 1, H, Hkv, hd, dtype, T=T)
    out_ref = ops.decode_attention(q, k, v, jnp.int32(pos), impl="ref")
    out_pal = ops.decode_attention(q, k, v, jnp.int32(pos), impl="pallas",
                                   interpret=True, block_k=blk)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_pal, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


def test_decode_ignores_stale_cache_slots():
    """Slots beyond pos hold garbage after restore — must not leak in."""
    B, T, H, Hkv, hd = 1, 256, 4, 2, 64
    q, k, v = _qkv(B, 1, H, Hkv, hd, jnp.float32, T=T)
    poisoned_k = k.at[:, 100:].set(1e4)
    poisoned_v = v.at[:, 100:].set(-1e4)
    out_clean = ops.decode_attention(q, k, v, jnp.int32(99), impl="pallas",
                                     interpret=True, block_k=64)
    out_poison = ops.decode_attention(q, poisoned_k, poisoned_v,
                                      jnp.int32(99), impl="pallas",
                                      interpret=True, block_k=64)
    np.testing.assert_allclose(np.asarray(out_clean), np.asarray(out_poison),
                               atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [256, 1024, 1000, 65536, 100])
def test_qsnap_roundtrip(n, dtype):
    x = (jax.random.normal(KEY, (n,), jnp.float32) * 5).astype(dtype)
    codes, scales, n_orig = ops.qsnap_compress(x, impl="pallas",
                                               interpret=True)
    back = ops.qsnap_decompress(codes, scales, n_orig, x.shape, dtype,
                                impl="pallas", interpret=True)
    xf = np.asarray(x, np.float32)
    err = np.abs(np.asarray(back, np.float32) - xf)
    # error bound: half a quantization step per 256-block
    bound = np.abs(xf).max() / 127.0 * 0.51 + 1e-6
    assert err.max() <= bound + (0.04 if dtype == jnp.bfloat16 else 0)


def test_qsnap_matches_host_codec_bitexact():
    x = jax.random.normal(KEY, (4096,), jnp.float32) * 3
    codes_d, scales_d, _ = ops.qsnap_compress(x, impl="pallas",
                                              interpret=True)
    codes_h, scales_h = compression.quantize_int8(np.asarray(x))
    np.testing.assert_array_equal(np.asarray(codes_d), codes_h)
    np.testing.assert_allclose(np.asarray(scales_d), scales_h, rtol=1e-7)


@pytest.mark.parametrize("rows", [257, 300])
def test_qsnap_padded_grid_matches_host_codec(rows):
    """Row counts with no tile-aligned divisor run on a padded grid; the
    pad rows must not leak into the codes, scales or dequantized values."""
    x = jax.random.normal(KEY, (rows * 256,), jnp.float32) * 3
    codes, scales = qsnap.qsnap_quantize(x, interpret=True)
    codes_h, scales_h = compression.quantize_int8(np.asarray(x))
    np.testing.assert_array_equal(np.asarray(codes), codes_h)
    np.testing.assert_array_equal(np.asarray(scales), scales_h)
    back = qsnap.qsnap_dequantize(codes, scales, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(back), compression.dequantize_int8(codes_h, scales_h,
                                                      rows * 256))


def test_qsnap_rounds_near_ties_like_host_codec():
    """Values within an ulp of (k + 0.5) * scale, where rounding the f32
    quotient and rounding the exact quotient disagree: the kernel and the
    jnp oracle must still give the host codec's codes."""
    rng = np.random.default_rng(3)
    base = rng.standard_normal((64, 256)).astype(np.float32)
    scale = np.abs(base).max(1, keepdims=True) * compression.INV127
    k = rng.integers(-127, 127, base.shape).astype(np.float32)
    x = ((k + 0.5) * scale).astype(np.float32)
    step = rng.integers(-1, 2, x.shape)
    x = np.where(step == 0, x, np.nextafter(
        x, np.where(step > 0, np.inf, -np.inf).astype(np.float32)))
    x[:, 0] = np.abs(base).max(1)                # keep each block's absmax
    x = x.reshape(-1)
    codes_h, scales_h = compression.quantize_int8(x)
    f32_codes = np.rint(x.reshape(-1, 256) / scales_h[:, None]).reshape(-1)
    assert (f32_codes != codes_h).sum() > 100, "data must hit double rounding"
    for codes, scales in (qsnap.qsnap_quantize(jnp.asarray(x), interpret=True),
                          ref.qsnap_ref(jnp.asarray(x))):
        np.testing.assert_array_equal(np.asarray(codes), codes_h)
        np.testing.assert_array_equal(np.asarray(scales), scales_h)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 2000), st.floats(0.01, 100.0),
       st.sampled_from(["float32", "bfloat16"]))
def test_qsnap_property(n, scale, dtype):
    """Property: roundtrip error bounded by per-block absmax/127/2."""
    rng = np.random.Generator(np.random.PCG64(n))
    x = (rng.standard_normal(n) * scale).astype(np.float32)
    codes, scales = compression.quantize_int8(x)
    back = compression.dequantize_int8(codes, scales, n)
    blocks = np.zeros(((n + 255) // 256) * 256, np.float32)
    blocks[:n] = x
    per_block_bound = (np.abs(blocks.reshape(-1, 256)).max(1) / 127.0 * 0.5
                       + 1e-7)
    err = np.abs(back - x)
    bounds = np.repeat(per_block_bound, 256)[:n]
    assert np.all(err <= bounds + 1e-6)
    assert codes.dtype == np.int8
    assert np.abs(codes.astype(np.int32)).max(initial=0) <= 127
