"""Peak rates of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e: 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, from the
Google Cloud documentation page "TPU v5e" (system architecture table). JAX
reports that chip as ``TPU v5 lite``. A device that is not in the table is
an error, never a default."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops_bf16: float      # FLOP/s per chip
    hbm_bytes_s: float     # bytes/s per chip
    hbm_bytes: float       # device memory per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        'Google Cloud documentation, "TPU v5e"'),
    "TPU v5e": Peak(197e12, 819e9, 16e9,
                    'Google Cloud documentation, "TPU v5e"'),
}


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peak rates for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
