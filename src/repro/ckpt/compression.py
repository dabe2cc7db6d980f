"""Checkpoint image codecs (paper Table 2: image size is the scaling lever).

Codecs operate on raw little-endian chunk bytes:
  * ``raw``       — identity.
  * ``zlib``      — lossless deflate (cheap CPU, good on low-entropy state).
  * ``int8``      — blockwise absmax int8 quantization of float leaves
                    (lossy; used for *swap-out* images of preempted jobs —
                    not for exact restarts).
  * ``int8+zlib`` — both.

The int8 codec's result equals ``repro.kernels.ref.qsnap_ref``'s exactly — the
Pallas kernel (device-side compression before D2H copy) and this host codec
are interchangeable, and tests assert bit-identical round-trips between them.
"""
from __future__ import annotations

import struct
import zlib
from typing import Tuple

import numpy as np

BLOCK = 256
_MAGIC = b"QS01"

# Scales are computed as absmax * (1/127) — an IEEE f32 multiply — rather
# than absmax / 127.  XLA rewrites division by a constant into a
# reciprocal multiply, so the multiply formulation is the only one that is
# bit-identical between this host codec, the jnp oracle, and the Pallas
# kernel (device-side encode).  Interchange tests depend on this.
INV127 = np.float32(1.0 / 127.0)

try:                                  # bf16 registers as kind='V', not 'f'
    import ml_dtypes
    _EXTRA_FLOATS = {np.dtype(ml_dtypes.bfloat16)}
except ImportError:                   # pragma: no cover
    _EXTRA_FLOATS = set()


def is_float_dtype(dt: np.dtype) -> bool:
    """Quantizable-float predicate shared with the device encode path.

    Host and device encoders must agree on which leaves quantize, or the
    same pytree produces different images on the two paths.  bf16 is the
    training dtype and must count even though numpy reports kind='V'.
    """
    dt = np.dtype(dt)
    return dt.kind == "f" or dt in _EXTRA_FLOATS


def quantize_int8(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """x: float array -> (int8 codes [n_pad], f32 scales [n_blocks])."""
    flat = np.asarray(x, dtype=np.float32).reshape(-1)
    n = flat.size
    n_pad = ((n + BLOCK - 1) // BLOCK) * BLOCK
    buf = np.zeros(n_pad, np.float32)
    buf[:n] = flat
    blocks = buf.reshape(-1, BLOCK)
    scales = np.max(np.abs(blocks), axis=1) * INV127
    scales = np.where(scales == 0, 1.0, scales).astype(np.float32)
    # Round the exact quotient, not the f32 one: the f64 quotient of two
    # f32 values lands on a half-integer only when the exact one does, and
    # the device encoders decide the same rounding exactly without a
    # correctly rounded divide (``repro.kernels.qsnap.int8_codes``).
    q = blocks.astype(np.float64) / scales[:, None]
    codes = np.clip(np.rint(q), -127, 127).astype(np.int8)
    return codes.reshape(-1), scales


def dequantize_int8(codes: np.ndarray, scales: np.ndarray,
                    n: int) -> np.ndarray:
    blocks = codes.reshape(-1, BLOCK).astype(np.float32) * scales[:, None]
    return blocks.reshape(-1)[:n]


def frame_int8(n: int, scales: np.ndarray, codes: np.ndarray) -> bytes:
    """Frame (codes, scales) of an n-element float chunk as a QS01 payload.

    Shared by the host codec and the device encode path
    (``repro.kernels.qsnap.qsnap_encode_chunks``) so both emit the exact
    same bytes — CAS digests over encoded bytes then dedup across the two.
    """
    return (_MAGIC + b"INT8"
            + struct.pack("<qq", n, scales.size)
            + scales.tobytes() + codes.tobytes())


def frame_raw(data: bytes) -> bytes:
    """Frame a non-float chunk's raw bytes as a QS01 passthrough payload."""
    return _MAGIC + b"RAWD" + data


def encode(data: bytes, dtype: np.dtype, codec: str) -> bytes:
    """Encode one chunk's raw bytes."""
    if codec == "raw":
        return data
    if codec == "zlib":
        return zlib.compress(data, level=1)
    if codec in ("int8", "int8+zlib"):
        dt = np.dtype(dtype)
        if not is_float_dtype(dt):
            payload = frame_raw(data)             # non-float: store raw
        else:
            arr = np.frombuffer(data, dtype=dt)
            codes, scales = quantize_int8(arr.astype(np.float32))
            payload = frame_int8(arr.size, scales, codes)
        if codec == "int8+zlib":
            return zlib.compress(payload, level=1)
        return payload
    raise ValueError(f"unknown codec {codec!r}")


def decode(data: bytes, dtype: np.dtype, codec: str) -> bytes:
    if codec == "raw":
        return data
    if codec == "zlib":
        return zlib.decompress(data)
    if codec in ("int8", "int8+zlib"):
        if codec == "int8+zlib":
            data = zlib.decompress(data)
        assert data[:4] == _MAGIC, "corrupt int8 chunk"
        kind = data[4:8]
        if kind == b"RAWD":
            return data[8:]
        n, n_scales = struct.unpack("<qq", data[8:24])
        off = 24
        scales = np.frombuffer(data[off:off + 4 * n_scales], np.float32)
        off += 4 * n_scales
        codes = np.frombuffer(data[off:], np.int8)
        out = dequantize_int8(codes, scales, n)
        return out.astype(np.dtype(dtype)).tobytes()
    raise ValueError(f"unknown codec {codec!r}")
