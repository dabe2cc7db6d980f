"""Jitted public wrappers around the Pallas kernels.

Layout conventions follow the model code ([B,S,H,hd]); wrappers transpose
to the kernels' [B,H,S,hd], pad sequence dims to block multiples (padding
is masked via ``kv_len``), and select an implementation:

  impl="pallas"    — real kernel (TPU) or interpret mode (CPU tests)
  impl="ref"       — the pure-jnp oracle (used by models on CPU)

On a CPU-only host ``default_impl()`` returns "ref"; tests force
impl="pallas", interpret=True to execute the kernel bodies.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.qsnap import qsnap_dequantize, qsnap_quantize

QSNAP_BLOCK = ref.QSNAP_BLOCK


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad_to(x: jax.Array, axis: int, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    impl: Optional[str] = None, interpret: bool = False,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,T,Hkv,hd] -> [B,S,H,hd]."""
    impl = impl or default_impl()
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    S, T = qt.shape[2], kt.shape[2]
    if impl == "ref":
        out = ref.flash_attention_ref(qt, kt, vt, causal=causal,
                                      window=window)
        return jnp.swapaxes(out, 1, 2)
    qt, _ = _pad_to(qt, 2, block_q)
    kt, kv_len = _pad_to(kt, 2, block_k)
    vt, _ = _pad_to(vt, 2, block_k)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               kv_len=kv_len, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return jnp.swapaxes(out[:, :, :S], 1, 2)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, *, impl: Optional[str] = None,
                     interpret: bool = False,
                     block_k: int = 512) -> jax.Array:
    """q: [B,1,H,hd]; k,v: [B,T,Hkv,hd]; pos scalar -> [B,1,H,hd]."""
    impl = impl or default_impl()
    qt = q[:, 0].swapaxes(0, 0)                      # [B,H,hd]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if impl == "ref":
        out = ref.decode_attention_ref(qt, kt, vt, pos)
        return out[:, None]
    kt, _ = _pad_to(kt, 2, block_k)
    vt, _ = _pad_to(vt, 2, block_k)
    out = decode_attention_bhd(qt, kt, vt, pos, block_k=block_k,
                               interpret=interpret)
    return out[:, None]


def qsnap_compress(x: jax.Array, *, impl: Optional[str] = None,
                   interpret: bool = False) -> Tuple[jax.Array, jax.Array, int]:
    """Any-shape float array -> (codes int8 [Npad], scales f32, n_orig)."""
    impl = impl or default_impl()
    n = x.size
    flat = x.reshape(-1)
    pad = (-n) % QSNAP_BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if impl == "ref":
        codes, scales = ref.qsnap_ref(flat)
    else:
        codes, scales = qsnap_quantize(flat, interpret=interpret)
    return codes, scales, n


def qsnap_decompress(codes: jax.Array, scales: jax.Array, n: int,
                     shape, dtype=jnp.float32, *,
                     impl: Optional[str] = None,
                     interpret: bool = False) -> jax.Array:
    impl = impl or default_impl()
    if impl == "ref":
        flat = ref.qsnap_dequant_ref(codes, scales, dtype)
    else:
        flat = qsnap_dequantize(codes, scales, dtype, interpret=interpret)
    return flat[:n].reshape(shape)
