"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark keeps its own table so that the yardstick cannot move with the
program.  A TPU kind missing here is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # dense bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}); add its row to "
                       "bench/lib/peaks.py")
    return PEAKS[device_kind]
