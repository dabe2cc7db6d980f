"""The ``QS01`` int8 image format, written out independently of the
program: one f32 scale per block of 256 elements (absmax times the f32
1/127), codes the integer nearest the exact quotient (ties to even),
clipped to +-127; framed as magic, kind, element and scale counts,
scales, codes."""
from __future__ import annotations

import struct

import numpy as np

BLOCK = 256
INV127 = np.float32(1.0 / 127.0)


def quantize(x: np.ndarray):
    flat = np.asarray(x, dtype=np.float32).reshape(-1)
    n = flat.size
    buf = np.zeros(-(-n // BLOCK) * BLOCK, np.float32)
    buf[:n] = flat
    blocks = buf.reshape(-1, BLOCK)
    scales = (np.max(np.abs(blocks), axis=1) * INV127).astype(np.float32)
    scales = np.where(scales == 0, np.float32(1.0), scales)
    q = blocks.astype(np.float64) / scales[:, None].astype(np.float64)
    codes = np.clip(np.rint(q), -127, 127).astype(np.int8)
    return codes.reshape(-1), scales


def encode(x: np.ndarray) -> bytes:
    codes, scales = quantize(x)
    return (b"QS01INT8" + struct.pack("<qq", x.size, scales.size)
            + scales.tobytes() + codes.tobytes())


def decode(x: np.ndarray) -> np.ndarray:
    """What a restore of ``x``'s int8 image must give, in ``x``'s dtype."""
    codes, scales = quantize(x)
    deq = (codes.reshape(-1, BLOCK).astype(np.float32) * scales[:, None])
    return deq.reshape(-1)[:x.size].astype(x.dtype).reshape(x.shape)
