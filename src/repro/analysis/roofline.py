"""Three-term roofline model, with peaks looked up by ``device_kind``.

    compute term    = FLOPs_per_chip / peak FLOP/s
    memory term     = HBM_bytes_per_chip / HBM bandwidth
    collective term = collective_bytes_per_chip / ICI bandwidth (one link)

All inputs come from the dry-run compiled artifact via analysis.hlo (per
device, trip-count adjusted).  MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D
(MoE) per analysis.flops — the ratio MODEL_FLOPS / HLO_FLOPs exposes remat /
redundancy waste.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    flops: float            # dense bf16 FLOP/s per chip
    hbm_bw: float           # HBM bytes/s per chip
    ici_bw: float           # interconnect bytes/s per link
    source: str


# Keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over four links "
               "(50 GB/s per link)"),
}

V5E = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; an unknown device raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_chip: float
    hbm_bytes_per_chip: float
    collective_bytes_per_chip: float
    peak_flops: float
    model_flops_per_chip: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Perfect-overlap bound: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        if self.flops_per_chip <= 0:
            return 0.0
        return self.model_flops_per_chip / self.flops_per_chip

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the ideal roofline achieved by the step-time bound:
        (useful compute time) / (bound step time)."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return (self.model_flops_per_chip / self.peak_flops) / t

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant, "step_time_s": self.step_time_s,
            "flops_per_chip": self.flops_per_chip,
            "hbm_bytes_per_chip": self.hbm_bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "model_flops_per_chip": self.model_flops_per_chip,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def roofline_terms(flops_per_chip: float, hbm_bytes_per_chip: float,
                   collective_bytes_per_chip: float,
                   model_flops_per_chip: float = 0.0, *,
                   device_kind: str) -> RooflineTerms:
    peaks = chip_peaks(device_kind)
    return RooflineTerms(
        compute_s=flops_per_chip / peaks.flops,
        memory_s=hbm_bytes_per_chip / peaks.hbm_bw,
        collective_s=collective_bytes_per_chip / peaks.ici_bw,
        flops_per_chip=flops_per_chip,
        hbm_bytes_per_chip=hbm_bytes_per_chip,
        collective_bytes_per_chip=collective_bytes_per_chip,
        peak_flops=peaks.flops,
        model_flops_per_chip=model_flops_per_chip,
    )
