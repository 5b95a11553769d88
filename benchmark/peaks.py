"""Published peaks of one chip, keyed by `device_kind` as jax reports it.

One table for every number the benchmark divides by a peak (`mfu`, the
kernels' roofline shares). A device kind that is not here is an error:
a utilisation against the wrong peak is worse than none.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip
interconnect per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops_per_s=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        ici_bits_per_s=1600e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


class UnknownDevice(Exception):
    pass


def for_device(device) -> Peaks:
    """The peaks of a jax device, or UnknownDevice: only the TPU, and
    only a kind in the table."""
    if device.platform != "tpu":
        raise UnknownDevice(
            f"platform is {device.platform!r}, not 'tpu': the benchmark "
            "measures the chip and does not fall back to another platform")
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device.device_kind!r} is not in "
            f"benchmark/peaks.py (known: {sorted(PEAKS)}); add its "
            "published peaks with their source") from None
