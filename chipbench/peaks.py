"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device missing from the table is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(PEAKS)} ({SOURCE})")
    return PEAKS[device_kind]
