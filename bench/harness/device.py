"""The chips a run holds, their published peaks, and their memory peak."""
from __future__ import annotations

from typing import List

# Published peaks per chip, keyed by jax's ``device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.  A kind
# that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}") from None


def require_chips(n: int) -> List:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < n:
        raise NoChip(f"{n} chips asked, JAX found {len(devs)}")
    peaks(devs[0].device_kind)
    return devs


def describe(devs) -> dict:
    import jax
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices())}


def memory_peak(devs) -> int:
    """``peak_bytes_in_use`` of the fullest chip, where the backend has it."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
