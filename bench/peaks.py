"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s in
bfloat16 and 16 GB of HBM at 819 GB/s per chip.  A device kind missing
from the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"peak_flops": 197e12, "mem_bw": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for {device_kind!r}") from None
