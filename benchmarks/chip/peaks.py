"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A device that is not in the table is an error, never a default: a share of
a peak computed against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


class UnknownDevice(LookupError):
    """The device kind has no entry in ``PEAKS``."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
