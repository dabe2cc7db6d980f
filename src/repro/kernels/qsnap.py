"""qsnap — blockwise int8 quantization kernel for checkpoint images.

The paper's scaling lever is checkpoint image *size* (Table 2, §5.2). On a
TPU fleet the equivalent hot path is the device->host copy: quantizing on
device (VMEM-resident, one pass) cuts it by ~4x for f32 state and ~2x for
bf16. Each 256-element block stores one f32
absmax scale + 256 int8 codes — the exact format ``repro.ckpt.compression``
writes, so device- and host-compressed images are interchangeable.

Tiles: [block_rows, 256] codes with [block_rows, 1] scales; the lane dim
(256) is 2x the 128-lane VPU width — one row = two vector registers.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.ckpt import compression
from repro.obs.trace import tracer

QSNAP_BLOCK = 256


def int8_codes(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Codes of f32 ``x`` under ``scale``: the integer nearest the exact
    quotient x / scale, ties to even, as f32 — what the host codec gets
    from an f64 divide.

    TPU f32 division need not round like IEEE division, so the quotient
    only proposes m = floor(|x| / scale), off by at most one; whether
    |x| lies above (m + 0.5) * scale is then decided exactly. ``scale``
    splits into two halves of 12 significant bits, so both products with
    m + 0.5 (8 bits) are exact, and |x| - (m + 0.5) * hi is exact wherever
    the comparison is close (Sterbenz). Only multiply, subtract and
    compare decide a code, and those round alike on every backend.
    """
    a = jnp.abs(x)
    m = jnp.floor(a / scale)
    half = m + 0.5
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(scale, jnp.int32) & jnp.int32(-4096),
        jnp.float32)
    above = a - half * hi
    lo_part = half * (scale - hi)
    odd = m - 2.0 * jnp.floor(m * 0.5) == 1.0
    up = (above > lo_part) | ((above == lo_part) & odd)
    c = jnp.minimum(m + up.astype(jnp.float32), 127.0)
    return jnp.where(x < 0, -c, c)


def _quant_kernel(x_ref, codes_ref, scales_ref):
    x = x_ref[...].astype(jnp.float32)                 # [rows, 256]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    # multiply, not /127: bit-identical to the host codec on every backend
    # (XLA lowers x/const to a reciprocal multiply anyway)
    scale = absmax * jnp.float32(1.0 / 127.0)
    scale = jnp.where(scale == 0, 1.0, scale)
    codes_ref[...] = int8_codes(x, scale).astype(jnp.int8)
    scales_ref[...] = scale


def _dequant_kernel(codes_ref, scales_ref, x_ref):
    codes = codes_ref[...].astype(jnp.float32)
    x_ref[...] = (codes * scales_ref[...]).astype(x_ref.dtype)


# int8 codes tile as (32, 128) on TPU; 32 rows also satisfies f32's 8
_ROW_TILE = 32


def _fit_block_rows(rows: int, cap: int) -> tuple:
    """(block_rows, padded_rows) for a grid over ``rows`` rows of 256.

    Mosaic accepts a block whose row count is a multiple of the tile or
    equals the array's whole row count. One block covers ``rows`` when it
    fits under ``cap``; otherwise rows are padded up to a multiple of
    ``_ROW_TILE`` and the block is the largest multiple of it, at most
    ``cap``, that divides the padded count (a true divisor: the grid must
    cover the array exactly).
    """
    if rows <= cap:
        return rows, rows
    padded = -(-rows // _ROW_TILE) * _ROW_TILE
    b = max(_ROW_TILE, cap - cap % _ROW_TILE)
    while padded % b:
        b -= _ROW_TILE
    return b, padded


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = rows - x.shape[0]
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def qsnap_quantize(x: jax.Array, *, block_rows: int = 256,
                   interpret: bool = False):
    """x: [N] float (N % 256 == 0) -> (codes int8 [N], scales f32 [N/256])."""
    n = x.shape[0]
    assert n % QSNAP_BLOCK == 0, n
    rows = n // QSNAP_BLOCK
    block_rows, padded = _fit_block_rows(rows, block_rows)
    # zero pad rows quantize to scale 1, codes 0 and are sliced off below
    xm = _pad_rows(x.reshape(rows, QSNAP_BLOCK), padded)
    codes, scales = pl.pallas_call(
        _quant_kernel,
        grid=(padded // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, QSNAP_BLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, QSNAP_BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, QSNAP_BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xm)
    return codes[:rows].reshape(-1), scales[:rows].reshape(-1)


def qsnap_dequantize(codes: jax.Array, scales: jax.Array, dtype=jnp.float32,
                     *, block_rows: int = 256, interpret: bool = False):
    """Inverse of qsnap_quantize -> [N] of ``dtype``."""
    n = codes.shape[0]
    rows = n // QSNAP_BLOCK
    block_rows, padded = _fit_block_rows(rows, block_rows)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(padded // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, QSNAP_BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, QSNAP_BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, QSNAP_BLOCK), dtype),
        interpret=interpret,
    )(_pad_rows(codes.reshape(rows, QSNAP_BLOCK), padded),
      _pad_rows(scales.reshape(rows, 1), padded))
    return out[:rows].reshape(-1)


def _encode_impl() -> str:
    # mirror of ops.default_impl(); inlined to keep kernels.ops -> qsnap
    # the only import direction between the two modules
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def qsnap_encode_chunks(arrs: Sequence[jax.Array], *,
                        impl: Optional[str] = None,
                        interpret: bool = False) -> List[bytes]:
    """Quantize chunk arrays on device into finished ``QS01`` payloads.

    For each float array this runs the blockwise int8 quantization on the
    *device* (Pallas kernel on TPU, jnp oracle elsewhere) and frames the
    result exactly as ``repro.ckpt.compression.encode(..., "int8")``
    would: the device→host copy carries int8 codes + one f32 scale per
    256 elements (~4x fewer bytes than f32 state), and the payload is
    byte-identical to the host codec's, so CAS digests over encoded bytes
    dedup across device- and host-compressed images.

    Non-float arrays fall back to the host RAWD framing (they are small:
    step counters, rng keys).  All device work is issued before the
    single batched ``jax.device_get``, so transfers overlap.
    """
    impl = impl or _encode_impl()
    staged = []                      # (index, n, device codes, scales)
    payloads: List[Optional[bytes]] = [None] * len(arrs)
    for i, arr in enumerate(arrs):
        if not compression.is_float_dtype(np.dtype(arr.dtype)):
            with tracer().span("ckpt/d2h", cat="ckpt",
                               args={"nbytes": arr.nbytes,
                                     "shape": tuple(arr.shape)}):
                host = jax.device_get(arr)
            payloads[i] = compression.frame_raw(
                np.ascontiguousarray(host).tobytes())
            continue
        flat = arr.reshape(-1)
        n = flat.size
        pad = (-n) % QSNAP_BLOCK
        if pad:
            flat = jnp.pad(flat, (0, pad))
        if impl == "ref":
            from repro.kernels import ref
            codes, scales = ref.qsnap_ref(flat)
        else:
            codes, scales = qsnap_quantize(flat.astype(jnp.float32),
                                           interpret=interpret)
        staged.append((i, n, codes, scales))
    if staged:
        pairs = [(c, s) for _, _, c, s in staged]
        with tracer().span("ckpt/d2h", cat="ckpt", args={
                "nbytes": sum(c.nbytes + s.nbytes for c, s in pairs),
                "arrays": len(pairs)}):
            fetched = jax.device_get(pairs)
        for (i, n, _, _), (codes, scales) in zip(staged, fetched):
            payloads[i] = compression.frame_int8(n, scales, codes)
    return payloads  # type: ignore[return-value]
