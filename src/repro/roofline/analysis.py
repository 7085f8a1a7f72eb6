"""Three-term roofline from compiled dry-run artifacts.

    compute    = HLO_FLOPs_global / (chips × peak_FLOP/s)
    memory     = HLO_bytes_global / (chips × HBM_bw)
    collective = collective_bytes_per_device / link_bw
                 (the assignment's 'collective_bytes / (chips × link_bw)' with
                  collective_bytes summed over chips — the SPMD module is
                  per-device, so per-device bytes × chips / (chips × link_bw)
                  reduces to this)

``cost_analysis()`` on the SPMD executable reports *per-device* FLOPs/bytes; we
scale by chip count for the global numerators, so the terms are per-device times —
directly comparable to a per-step wall clock.

Peaks come from :data:`PEAKS`, keyed by ``device_kind`` as JAX reports it
(``jax.devices()[0].device_kind``); a kind that is not in the table is an
error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float        # FLOP/s
    hbm_bw: float            # B/s
    ici_link_bw: float       # B/s per chip-to-chip link
    source: str


#: device_kind -> peaks. JAX names a v5e chip "TPU v5 lite".
PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interconnect per chip over 4 links
        ici_link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e" (per chip: 197 '
               'TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s '
               'interconnect)'),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


@dataclass
class RooflineTerms:
    chips: int
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    collective_bytes_per_device: float
    peaks: DevicePeaks
    model_flops: float = 0.0     # 6·N·D (or 6·N_active·D)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops_per_device / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_link_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def model_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS_global — 'useful compute' fraction; catches
        remat/redundancy waste. >1 means HLO under-counts (fusion estimates)."""
        total = self.hlo_flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the dominant-term roofline that *useful* model FLOPs
        represent: (MODEL_FLOPS/(chips·peak)) / bound_s. 1.0 = the step is exactly
        as long as the useful math at peak — the hillclimb score."""
        useful_s = self.model_flops / (self.chips * self.peaks.flops_bf16)
        return useful_s / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> dict:
        return {
            "chips": self.chips,
            "peaks_source": self.peaks.source,
            "hlo_flops_per_device": self.hlo_flops_per_device,
            "hlo_bytes_per_device": self.hlo_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops_ratio": self.model_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D for training, 2·N·D for inference (fwd only); MoE uses
    active params. D = tokens processed by the step."""
    n = cfg.active_params_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
