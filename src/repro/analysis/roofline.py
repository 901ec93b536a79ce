"""Roofline model over published per-chip peaks, keyed by ``device_kind``.

    compute_s    = HLO_FLOPs_global   / (chips * peak FLOP/s)   [bf16 MXU]
    memory_s     = HLO_bytes_global   / (chips * HBM bytes/s)
    collective_s = collective_bytes_global / (chips * ICI bytes/s per link)

``cost_analysis``/HLO parsing yield *per-chip* numbers (spike-verified); globals
are per-chip x chips, so the chips cancel — the terms are per-chip seconds. The
dominant term is the bottleneck; `model_flops / hlo_flops` measures how much of
the compiled compute is algorithmically useful (remat / noise-sim / dispatch
overheads show up here).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # chip-to-chip bytes/s per link
    source: str


# keyed by jax.Device.device_kind
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interconnect over 4 links
        ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a chip not in :data:`PEAKS` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def model_flops(cfg, shape, n_params: int, n_active: int) -> float:
    """Analytic useful FLOPs per step: 6*N*D train, 2*N*D forward."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def active_params(cfg, n_params: int, spec_tree=None) -> int:
    """Params touched per token (MoE: k of E experts)."""
    if cfg.num_experts and cfg.experts_per_token:
        # expert share of parameters
        E, K = cfg.num_experts, cfg.experts_per_token
        F = cfg.moe_d_ff or cfg.d_ff
        n_moe_layers = sum(cfg.moe_layer_mask())
        expert_params = n_moe_layers * E * 3 * cfg.d_model * F
        return int(n_params - expert_params * (1 - K / E))
    return n_params


@dataclasses.dataclass
class Roofline:
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    chips: int
    model_flops_global: float
    device_kind: str

    def terms(self) -> dict:
        peaks = peaks_for(self.device_kind)
        compute_s = self.flops_per_chip / peaks.flops
        memory_s = self.bytes_per_chip / peaks.hbm_bw
        coll_s = self.coll_bytes_per_chip / peaks.ici_bw
        terms = {"compute_s": compute_s, "memory_s": memory_s,
                 "collective_s": coll_s}
        dom = max(terms, key=terms.get)
        bound = max(terms.values())
        hlo_flops_global = self.flops_per_chip * self.chips
        useful = (self.model_flops_global / hlo_flops_global
                  if hlo_flops_global else 0.0)
        # roofline fraction: useful-compute time / bound time (how close the
        # step is to the compute roofline if overheads vanished)
        ideal_s = self.model_flops_global / (self.chips * peaks.flops)
        return dict(
            terms,
            dominant=dom,
            step_time_lower_bound_s=bound,
            model_flops_global=self.model_flops_global,
            hlo_flops_global=hlo_flops_global,
            useful_flops_ratio=useful,
            ideal_compute_s=ideal_s,
            roofline_fraction=(ideal_s / bound) if bound > 0 else 0.0,
        )
