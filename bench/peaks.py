"""Published peaks of the chips the benchmark runs on, keyed by
`device_kind` as JAX reports it.

This is the benchmark's own copy of the TPU v5e row of
`repro/device.py`, so that no change to the program can move the
yardstick a roofline share is read against. A device missing from the
table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
               'bf16, 16 GB HBM at 819 GB/s'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
