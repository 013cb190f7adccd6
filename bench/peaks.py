"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Copied from ``repro.launch.mesh.CHIP_PEAKS``, so that no change to the
program moves the yardstick. TPU v5e: Google Cloud documentation, "TPU
v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s interconnect over four
links.
"""
from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    bf16_flops: float  # FLOP/s per chip
    hbm_bytes_per_s: float  # per chip
    ici_bytes_per_s: float  # per chip-to-chip link


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip; a kind not in the table raises."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}"
        ) from None
