"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 819 GB/s
of HBM bandwidth, 1,600 Gbit/s of chip-to-chip interconnect over 4 links
(50 GB/s per link).  A device kind missing from the table is an error:
there is no default peak.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops_bf16: float  # FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per interconnect link


PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}

# the part the multi-pod dry-run compiles for
DRYRUN_TARGET = "TPU v5 lite"


def peaks(device_kind: str) -> DevicePeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None


BYTES = {
    "f32": 4,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "s8": 1,
    "u8": 1,
    "pred": 1,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "s16": 2,
    "u16": 2,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
}
