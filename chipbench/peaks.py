"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s (Google Cloud documentation, "TPU v5e"). A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind][key]
