"""Roofline terms from dry-run analyses (TPU v5e targets).

Terms (per training/serving step, seconds):
    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

``compiled.cost_analysis()`` operates on the PARTITIONED module, so its
'flops' / 'bytes accessed' are already per-device — equivalent to the
assignment's HLO_FLOPs / (chips x peak) with global numbers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class HW:
    peak_flops: float                # bf16 FLOP/s per chip
    hbm_bw: float                    # HBM bytes/s per chip
    ici_bw: float                    # bytes/s per ICI link


V5E_KIND = "TPU v5 lite"             # jax's device_kind of a TPU v5e chip

# Published peaks per chip, keyed by ``device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links, i.e. 50e9 bytes/s per link.
PEAKS: Dict[str, HW] = {V5E_KIND: HW(peak_flops=197e12, hbm_bw=819e9,
                                     ici_bw=50e9)}


def peaks(device_kind: str) -> HW:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}") from None


@dataclass
class RooflineTerms:
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    model_flops_global: float        # 6*N*D (or 6*N_active*D for MoE)
    chips: int
    hw: HW

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_device / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / self.hw.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound(self) -> float:
        """Perfect-overlap bound: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS — remat/padding/dispatch waste detector."""
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization achievable at the roofline bound."""
        t = self.step_time_lower_bound
        if t <= 0:
            return 0.0
        return self.model_flops_global / (self.chips * self.hw.peak_flops * t)

    def as_dict(self) -> Dict[str, float]:
        return {
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "model_flops_global": self.model_flops_global,
            "chips": self.chips,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_fraction": self.useful_flops_fraction,
            "mfu_bound": self.mfu_bound,
            "step_time_lower_bound": self.step_time_lower_bound,
        }


def roofline_from_analysis(cost, collective_bytes_per_device: float,
                           model_flops_global: float, chips: int,
                           hw: HW) -> RooflineTerms:
    """``cost`` is a ``compiled.cost_analysis()`` dict (None where the
    backend gives none) or a hand-built {'flops', 'bytes accessed'} dict."""
    cost = cost or {}
    return RooflineTerms(
        flops_per_device=float(cost.get("flops", 0.0)),
        hbm_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_bytes_per_device=collective_bytes_per_device,
        model_flops_global=model_flops_global,
        chips=chips, hw=hw)
