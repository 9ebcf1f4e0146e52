"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A device that is not in the table is an error, never a default: a
roofline share or an MFU against a guessed peak is not a measurement.
"""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
}

SOURCE = "Google Cloud documentation, TPU v5e system architecture"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; KeyError when unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
