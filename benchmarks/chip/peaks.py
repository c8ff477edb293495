"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a share
of a peak is only meaningful against the chip it ran on.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises UnknownDevice."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
