"""Roofline arithmetic against published per-chip peaks.

The three terms (seconds) for one compiled step on an N-chip mesh:

  compute_s    = HLO_FLOPs / (chips * peak FLOP/s)
  memory_s     = HLO_bytes / (chips * HBM bytes/s)
  collective_s = collective_bytes / (chips * ICI bytes/s)

FLOPs/bytes come from ``compiled.cost_analysis()`` (per-device program ×
device count is already folded in by the dry-run, which records per-device
numbers — pass per-device values with chips=1, or totals with the mesh
size). ``collective_bytes`` is parsed from the post-SPMD HLO by
``repro.launch.dryrun.collective_bytes``.

Peaks live in :data:`PEAKS`, keyed by ``jax.Device.device_kind``; a
device that is not in the table is an error, never a default.
"""
from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip
# interconnect. The MXU's bf16 rate is also the ceiling of an fp32
# matmul (which runs as several bf16 passes).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}

# The chip the dry runs and the kernels' analytic roofline terms target
# (TPU v5e; jax reports its device_kind as "TPU v5 lite").
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; raises for a
    device the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def terms(*, flops: float, bytes_accessed: float, collective_bytes: float,
          n_devices: int, device_kind: str) -> dict:
    p = peaks(device_kind)
    compute_s = flops / (n_devices * p["flops"])
    memory_s = bytes_accessed / (n_devices * p["hbm_bytes_per_s"])
    collective_s = collective_bytes / (n_devices * p["ici_bytes_per_s"])
    bottleneck = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]
    step_s = max(compute_s, memory_s, collective_s)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "step_s": step_s,
        # fraction of roofline the *compute* term occupies — the score:
        # 1.0 means the step is pure MXU with nothing else dominant.
        "roofline_fraction": compute_s / step_s if step_s > 0 else 0.0,
    }


def model_flops(n_params_active: float, tokens: float) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) for a train step;
    2·N·D for inference-only steps (pass the matching factor)."""
    return 6.0 * n_params_active * tokens


def per_device(rec: dict) -> dict:
    """Extract per-device roofline inputs from a dry-run JSON record.
    cost_analysis FLOPs/bytes are per-device for SPMD programs; so is the
    parsed per-device HLO collective footprint — use chips=1."""
    return {
        "flops": rec["cost"]["flops"],
        "bytes_accessed": rec["cost"]["bytes_accessed"],
        "collective_bytes": rec["collectives"]["total_bytes"],
        "n_devices": 1,
    }
