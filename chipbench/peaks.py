"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  JAX names that chip ``"TPU v5 lite"``.

A device that is not in the table is an error: a roofline share or an
MFU against a guessed peak would be a number of nobody's chip.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # dense bf16 FLOP/s of one chip
    hbm_bw: float       # HBM bytes/s of one chip
    hbm_bytes: float    # HBM capacity of one chip


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


class UnknownDeviceError(KeyError):
    """The device kind has no row in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
