"""Published peaks of each chip the benchmark runs on, keyed by
`jax.Device.device_kind`.  A kind that is not here is an error, never a
default: a roofline share against the wrong peak is a wrong number.

v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s
HBM, 16 GB of HBM per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float        # dense bf16
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
