"""Three-term roofline model for TPU v5e (assignment constants).

    compute_s    = HLO_FLOPs_per_device / peak_flops
    memory_s     = HLO_bytes_per_device / hbm_bw
    collective_s = collective_wire_bytes_per_device / (links_per_chip? ->
                   assignment formula: chips cancel because HLO is already
                   the per-device program; we divide by one link_bw)

The compiled module is the per-device SPMD program, so cost_analysis()
already reports per-chip numbers — no further division by chip count.
MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) is the analytic useful work;
MODEL_FLOPS / (HLO_FLOPs * chips) exposes remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.models.common import ModelConfig

PEAK_BF16 = 197e12          # FLOP/s per chip
PEAK_INT8 = 394e12          # TOPS int8 (MXU 2x) — MUXQ's uniform-int8 claim
HBM_BW = 819e9              # B/s per chip
ICI_BW = 50e9               # B/s per link (assignment figure)


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float            # per device
    hlo_bytes: float            # per device
    coll_bytes: float           # per device
    model_flops: float          # analytic, global
    chips: int
    compute_s_int8: Optional[float] = None

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """No-overlap upper bound on step time."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_fraction(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips)."""
        tot = self.hlo_flops * self.chips
        return self.model_flops / tot if tot else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the roofline step time (the score)."""
        denom = self.step_s * self.chips * PEAK_BF16
        return self.model_flops / denom if denom else 0.0

    def as_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant, step_s=self.step_s,
                 useful_fraction=self.useful_fraction, mfu_bound=self.mfu_bound)
        return d


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Non-embedding parameter count (analytic, matches init_params)."""
    d, f = cfg.d_model, cfg.d_ff
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attn = d * (h + 2 * kv) * dh + h * dh * d
    mlp = d * 2 * f + f * d if cfg.mlp_type == "swiglu" else 2 * d * f
    if cfg.is_mla:
        r, nope, rope, v = (cfg.kv_lora_rank, cfg.qk_nope_dim,
                            cfg.qk_rope_dim, cfg.v_head_dim)
        attn = d * (h * (nope + rope) + r + rope) + r * h * (nope + v) + h * v * d
    fe, fs = cfg.d_expert, cfg.d_ff_shared
    n = 0
    for kind, ffn in zip(cfg.blocks, cfg.ffn_kinds):
        if kind == "mamba":
            di, ns, hs = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
            n += d * 2 * di + d * (2 * ns + hs) + di * d
        elif ffn == "experts":
            e = cfg.top_k if active_only else cfg.n_experts
            n += attn + e * (d * 2 * fe + fe * d)
            if fs:
                n += d * 2 * fs + fs * d
        else:
            n += attn + mlp
    if cfg.shared_attn_every:  # zamba2 shared block counts once (weights shared)
        n += attn + mlp
    if cfg.n_enc_layers:
        n += cfg.n_enc_layers * (attn + mlp)
        n += cfg.n_layers * (d * h * dh + d * 2 * kv * dh + h * dh * d)  # cross
    return n


def model_flops(cfg: ModelConfig, tokens: int, mode: str) -> float:
    """6·N·D train / 2·N·D forward-only (N = active non-embedding params)."""
    n = param_count(cfg, active_only=True)
    per_tok = 6 * n if mode == "train" else 2 * n
    return float(per_tok) * tokens


def make_roofline(cost: Dict, coll: Dict, cfg: ModelConfig, tokens: int,
                  mode: str, chips: int, int8_fraction: float = 0.0) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    byt = float(cost.get("bytes accessed", 0.0))
    cb = float(coll.get("total", 0.0))
    compute_s = flops / PEAK_BF16
    # int8_fraction of matmul flops run at 2x on the MXU (MUXQ uniform-int8)
    compute_s_int8 = (flops * (1 - int8_fraction) / PEAK_BF16
                      + flops * int8_fraction / PEAK_INT8)
    return Roofline(
        compute_s=compute_s,
        memory_s=byt / HBM_BW,
        collective_s=cb / ICI_BW,
        hlo_flops=flops, hlo_bytes=byt, coll_bytes=cb,
        model_flops=model_flops(cfg, tokens, mode),
        chips=chips, compute_s_int8=compute_s_int8,
    )
