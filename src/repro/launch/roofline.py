"""Roofline-term extraction from a compiled dry-run artifact.

  compute term    = HLO_FLOPs / peak_FLOP/s            (per device)
  memory term     = HLO_bytes / HBM_bw                 (per device)
  collective term = collective_bytes / link_bw         (per device)

FLOPs / bytes / collective bytes come from the loop-aware HLO walker in
hlo_cost.py (XLA's own cost_analysis counts while bodies once — wrong
for scan-over-layers models; we record it alongside for reference).

Score reported per cell:
  roofline_fraction = t_ideal / t_bound, where
    t_ideal = max(model_flops/chips/peak,  min_bytes/HBM_bw)
      — the time physics requires for the USEFUL work (6·N·D compute,
        one pass over weights+cache+activations), and
    t_bound = max(compute, memory, collective achieved terms).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.device import V5E, peaks

from . import hlo_cost

# the dry-run meshes are TPU v5e pods
_CHIP = peaks(V5E)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-device HLO flops (loop-aware)
    hbm_bytes: float             # per-device bytes accessed
    coll_bytes: float            # per-device collective wire bytes
    coll_detail: Dict[str, float]
    model_flops: float           # 6*N*D (global, useful)
    min_bytes: float             # per-device unavoidable HBM traffic
    chips: int
    xla_cost: Optional[dict] = None   # raw (loop-unaware) reference

    @property
    def t_compute(self):
        return self.flops / _CHIP.flops

    @property
    def t_memory(self):
        return self.hbm_bytes / _CHIP.hbm_bw

    @property
    def t_collective(self):
        return self.coll_bytes / _CHIP.ici_bw

    @property
    def t_bound(self):
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def t_ideal(self):
        t_c = (self.model_flops / self.chips) / _CHIP.flops
        t_m = self.min_bytes / _CHIP.hbm_bw
        return max(t_c, t_m)

    @property
    def bottleneck(self):
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self):
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self):
        return self.t_ideal / self.t_bound if self.t_bound else 0.0

    def as_dict(self):
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.coll_bytes,
            "collective_detail": self.coll_detail,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "t_ideal_s": self.t_ideal,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "min_bytes_per_device": self.min_bytes,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "xla_cost_reference": self.xla_cost,
        }


def analyze(compiled, *, model_flops: float, chips: int,
            min_bytes: float, hlo_text: Optional[str] = None
            ) -> Roofline:
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hlo_cost.analyze_text(text)
    xla = None
    try:
        raw = compiled.cost_analysis()
        if isinstance(raw, list):
            raw = raw[0]
        xla = {"flops": float(raw.get("flops", 0.0)),
               "bytes accessed": float(raw.get("bytes accessed", 0.0))}
    except Exception:  # noqa: BLE001
        pass
    return Roofline(flops=cost.flops, hbm_bytes=cost.hbm_bytes,
                    coll_bytes=cost.coll_bytes,
                    coll_detail=dict(cost.coll_detail),
                    model_flops=model_flops, min_bytes=min_bytes,
                    chips=chips, xla_cost=xla)


def model_flops_for(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode D = batch tokens."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
