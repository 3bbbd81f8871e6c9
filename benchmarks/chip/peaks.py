"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GiB HBM per chip. A device
kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_per_s: float     # dense bf16 matrix throughput
    bytes_per_s: float     # HBM bandwidth
    hbm_bytes: int         # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16 * 2**30,
                         'Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; ``KeyError`` for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
