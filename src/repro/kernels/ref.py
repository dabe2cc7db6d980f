"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

All reference functions use f32 accumulation, matching the kernels' VMEM
accumulator dtype, so assert_allclose tolerances stay tight even for bf16
inputs.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.qsnap import int8_codes

NEG_INF = -1e30
QSNAP_BLOCK = 256


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        kv_len: Optional[int] = None) -> jax.Array:
    """q: [B,H,S,hd]; k,v: [B,Hkv,T,hd] (GQA) -> [B,H,S,hd]."""
    B, H, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, S, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgsd,bktd->bkgst", qg, kf) / math.sqrt(hd)
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(T)[None, :]
    rel = qp - kp
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= rel >= 0
    if window is not None:
        mask &= rel < window
    if kv_len is not None:
        mask &= kp < kv_len
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", p, vf)
    return o.reshape(B, H, S, hd).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         pos: jax.Array) -> jax.Array:
    """q: [B,H,hd]; k,v: [B,Hkv,T,hd]; pos scalar -> [B,H,hd].

    Attends over cache slots 0..pos (inclusive).
    """
    B, H, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    mask = jnp.arange(T) <= pos
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


def qsnap_ref(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Blockwise absmax int8 quantization. x: [N] (N % 256 == 0).

    Returns (codes int8 [N], scales f32 [N/256]). Matches
    ``repro.ckpt.compression.quantize_int8`` bit-for-bit (both sides use
    the absmax * (1/127) multiply — see ``compression.INV127`` — and
    round the exact quotient, see ``qsnap.int8_codes``).
    """
    xf = x.astype(jnp.float32).reshape(-1, QSNAP_BLOCK)
    scales = jnp.max(jnp.abs(xf), axis=1) * jnp.float32(1.0 / 127.0)
    scales = jnp.where(scales == 0, 1.0, scales)
    codes = int8_codes(xf, scales[:, None])
    return codes.astype(jnp.int8).reshape(-1), scales


def qsnap_dequant_ref(codes: jax.Array, scales: jax.Array,
                      dtype=jnp.float32) -> jax.Array:
    blocks = codes.reshape(-1, QSNAP_BLOCK).astype(jnp.float32)
    return (blocks * scales[:, None]).reshape(-1).astype(dtype)
