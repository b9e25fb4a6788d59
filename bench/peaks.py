"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per
chip. A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row of ``device_kind``; raises for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
