"""Roofline-term derivation from a compiled dry-run artifact.

Terms (seconds, PER-DEVICE — the post-SPMD HLO module is the per-device
program):

  compute term    = device_FLOPs / peak_FLOP/s
  memory term     = device bytes accessed / HBM bw
  collective term = device collective bytes / link bw (ICI and DCN separate)

Costs come from :mod:`repro.launch.hlo_cost`, which (unlike XLA's
``cost_analysis()``) multiplies while-loop bodies by their trip counts —
essential for scan-over-layers models. The raw XLA numbers are retained as
``xla_flops_unrolled`` for cross-checking.

Bytes are counted at fusion boundaries (operands + outputs), an upper-bound
proxy for HBM traffic. All-reduce bytes get a 2x ring factor.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import Dict, Optional

from repro.launch.hlo_cost import Cost, analyze_hlo
from repro.launch.mesh import DCN_BW, HBM_BW, ICI_BW, PEAK_FLOPS_BF16


@dataclass
class Roofline:
    # per-device quantities
    flops: float
    bytes_accessed: float
    transcendentals: float
    ici_bytes: float
    dcn_bytes: float
    chips: int
    model_flops: float = 0.0          # analytic useful FLOPs (GLOBAL)
    coll_by_kind: Dict[str, float] = field(default_factory=dict)
    xla_flops_unrolled: float = -1.0  # XLA cost_analysis (loops counted once)
    per_device_peak_memory: float = -1.0

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def t_ici(self) -> float:
        return self.ici_bytes / ICI_BW

    @property
    def t_dcn(self) -> float:
        return self.dcn_bytes / DCN_BW

    @property
    def t_collective(self) -> float:
        return self.t_ici + self.t_dcn

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def step_time_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mfu_bound(self) -> float:
        """MFU if the step ran exactly at the roofline bound."""
        t = self.step_time_bound
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS_BF16 * t)

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_ici=self.t_ici, t_dcn=self.t_dcn,
                 t_collective=self.t_collective, dominant=self.dominant,
                 useful_ratio=self.useful_ratio,
                 step_time_bound=self.step_time_bound,
                 mfu_bound=self.mfu_bound)
        return d


def roofline_from_compiled(compiled, chips: int, model_flops: float,
                           pod_size: int = 256) -> Roofline:
    cost = analyze_hlo(compiled.as_text(), pod_size=pod_size)
    xla_flops = float(compiled.cost_analysis().get("flops", -1.0))
    ma = compiled.memory_analysis()
    peak = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                 + ma.output_size_in_bytes)
    return Roofline(
        flops=cost.flops, bytes_accessed=cost.bytes,
        transcendentals=cost.transcendentals,
        ici_bytes=cost.ici_bytes, dcn_bytes=cost.dcn_bytes, chips=chips,
        model_flops=model_flops, coll_by_kind=dict(cost.coll_by_kind),
        xla_flops_unrolled=xla_flops, per_device_peak_memory=peak,
    )


def model_flops_estimate(cfg, shape) -> float:
    """Analytic 'useful' FLOPs (GLOBAL): 6·N_active·T train, 2·N_active·T
    prefill (+ causal attention term), decode adds KV-cache attention."""
    n_active = cfg.n_active_params()
    hd = cfg.resolved_head_dim
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        base = 6.0 * n_active * tokens
        if cfg.n_heads:
            # causal attention: 2(qk)+2(av), fwd+bwd(x2) halves for causality
            att = 6.0 * cfg.n_layers * cfg.n_heads * hd * shape.seq_len * tokens / 2
            base += att
        return base
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        base = 2.0 * n_active * tokens
        if cfg.n_heads:
            att = 2.0 * cfg.n_layers * cfg.n_heads * hd * shape.seq_len * tokens
            base += att / 2
        return base
    tokens = shape.global_batch
    base = 2.0 * n_active * tokens
    if cfg.n_heads:
        att = 4.0 * cfg.n_heads * hd * shape.seq_len * cfg.n_layers * tokens
        base += att
    return base


__all__ = ["Roofline", "roofline_from_compiled", "model_flops_estimate"]
