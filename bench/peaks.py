"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/
docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
JAX names the chip "TPU v5 lite". A kind that is not here is an error:
no number is ever reported against a guessed or a CPU peak.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; raises ``KeyError`` if unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return dict(PEAKS[device_kind])
