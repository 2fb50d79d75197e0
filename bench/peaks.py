"""Published per-chip peaks, keyed by the `device_kind` JAX reports.

A device kind missing from the table is an error, never a default: a
roofline share against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # bf16 FLOP/s (the MXU's peak)
    hbm_bw: float       # bytes/s
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
