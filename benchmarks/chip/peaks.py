"""Chip peaks, keyed by JAX's ``device_kind``.

A device that is not listed is an error, never a default.  The
operations and bytes a step's work requires are counted by the
configuration's block module (``blocks/<block>.py``).
"""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB of HBM per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}: add it to "
                       "benchmarks/chip/peaks.py with its source")
    return PEAKS[kind]
