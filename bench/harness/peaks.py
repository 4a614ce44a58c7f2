"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect. The bf16 rate is also the ceiling of a float32 matmul,
which the MXU runs as one or more bf16 passes. A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 1600e9 / 8, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, bytes_: float, device_kind: str) -> float:
    """The least time one chip could take: the larger of the compute and
    the memory bound."""
    p = peaks(device_kind)
    return max(flops / p["flops"], bytes_ / p["hbm_bytes_per_s"])
