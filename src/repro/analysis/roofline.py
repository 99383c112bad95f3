"""Three-term roofline from compiled dry-run artifacts.

  compute term    = HLO_FLOPs_per_device / peak_FLOPs
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = wire_bytes_per_device(ICI)/ICI_bw + (DCN)/DCN_bw

Peaks come from ``analysis/peaks.py`` for the cell's ``device_kind`` (the
dry-run names its target kind).

``cost_analysis()`` reports per-device FLOPs/bytes (verified: scan bodies
are multiplied by trip count); collective bytes come from analysis/hlo.py.
MODEL_FLOPS uses the classic 6·N·D (train) / 2·N·D (inference) with
N_active for MoE — the ratio MODEL_FLOPS / HLO_FLOPs exposes remat and
padding waste.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax

from repro.analysis.peaks import peaks
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.headroom import RooflineTerms


# ---------------------------------------------------------------------------
# parameter counting (exact, from the abstract param tree)
# ---------------------------------------------------------------------------

def param_count(cfg: ArchConfig) -> int:
    import math
    from repro.models import registry
    tree = registry.abstract_params(cfg)
    return sum(math.prod(l.shape)
               for l in jax.tree_util.tree_leaves(tree))


def active_param_count(cfg: ArchConfig) -> int:
    """Per-token active params: replace num_experts by experts_per_token."""
    import math
    n = param_count(cfg)
    if not cfg.num_experts:
        return n
    from repro.models import registry
    tree = registry.abstract_params(cfg)
    expert_total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(p, "key", "")) for p in path]
        if "moe" in keys and any(k in ("wi", "wg", "wo") for k in keys):
            expert_total += math.prod(leaf.shape)
    active_frac = (cfg.experts_per_token / cfg.num_experts)
    return n - expert_total + int(expert_total * active_frac)


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6·N·D for train, 2·N·D for inference forward (D = processed tokens)."""
    n_active = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch * 1      # decode: one token per sequence
    return 2.0 * n_active * tokens


# ---------------------------------------------------------------------------
# analytic HBM-traffic model
# ---------------------------------------------------------------------------
# The HLO-parsed byte count is an *unfused upper bound* (XLA:CPU materializes
# far more fusion boundaries than a TPU build), so the memory term uses a
# first-principles model; the parsed bytes are reported alongside.

def analytic_memory_bytes(cfg: ArchConfig, shape: ShapeConfig,
                          n_chips: int, n_model: int = 16) -> float:
    """Per-device HBM bytes per step (read+write counted once each)."""
    P = param_count(cfg)
    P_active = active_param_count(cfg)
    dt = 2  # bf16
    n_batch_shards = n_chips // n_model
    train = shape.kind == "train"
    passes = {"train": 4, "prefill": 1, "decode": 1}[shape.kind]
    # weights: each device reads its TP shard of the *active* params every
    # pass (fwd + remat-refwd + 2 bwd matmuls per weight)
    weights = P_active / n_model * dt * passes
    total = weights
    if train:
        # optimizer: grads (fp32 w+r) + m/v (r+w) + param (r+w), ZeRO-sharded
        state_b = 2 if cfg.opt_state_dtype == "bfloat16" else 4
        shard = P / n_chips
        total += shard * (2 * 4 + 2 * 2 * state_b + 2 * dt)
    # activations
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.global_batch * shape.seq_len
    tok_loc = max(tokens // n_batch_shards, 1)
    D, H, Kv, hd, F = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                       cfg.hd, cfg.d_ff)
    act_passes = 3 if train else 1   # fwd + remat refwd + bwd
    F_eff = F * (cfg.experts_per_token if cfg.num_experts else 1)
    per_layer = (4 * D + 2 * (H * hd + Kv * hd) / n_model
                 + 3 * F_eff / n_model)
    total += cfg.num_layers * tok_loc * per_layer * dt * act_passes
    # attention score/prob traffic (XLA chunked path, fp32)
    S = shape.seq_len
    n_attn = sum(1 for l in range(cfg.num_layers) if cfg.is_attn_layer(l))
    if shape.kind != "decode":
        eff_ctx = min(cfg.sliding_window or S, S)
        probs = n_attn * tok_loc * eff_ctx * (H / n_model) * 4 * act_passes
        total += 2 * probs      # scores + probs
    else:
        # decode reads the whole (sharded) KV cache once per step
        cache_tokens = min(cfg.sliding_window or S, S)
        kv = n_attn * shape.global_batch * cache_tokens * 2 * Kv * hd * dt
        total += kv / n_chips
    # recurrent-state traffic (mamba / rwkv)
    if cfg.family in ("hybrid", "ssm"):
        n_mix = cfg.num_layers - n_attn if cfg.family == "hybrid" \
            else cfg.num_layers
        d_inner = (cfg.ssm_expand * D if cfg.family == "hybrid"
                   else D)
        state = cfg.ssm_d_state if cfg.family == "hybrid" else cfg.rwkv_head_dim
        total += (n_mix * tok_loc * d_inner / n_model * state * 4
                  * act_passes * 0.25)   # chunked scan touches state/chunk
    return float(total)


# ---------------------------------------------------------------------------
# roofline assembly
# ---------------------------------------------------------------------------

@dataclass
class CellRoofline:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    flops_per_device: float
    bytes_per_device: float
    wire_ici: float
    wire_dcn: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float
    peak_memory_bytes: float
    argument_bytes: float
    device_kind: str
    collectives: dict = field(default_factory=dict)

    def terms(self) -> RooflineTerms:
        return RooflineTerms(self.compute_s, self.memory_s, self.collective_s)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of ideal compute-bound throughput (MFU-like, modeled)."""
        ideal = self.model_flops / (
            self.n_chips * peaks(self.device_kind).bf16_flops)
        return ideal / self.step_s if self.step_s else 0.0

    def to_dict(self):
        d = dict(self.__dict__)
        d["step_s"] = self.step_s
        d["roofline_fraction"] = self.roofline_fraction
        return d

    def to_records(self):
        """Emit this cell in the unified experiment Record schema."""
        from repro.experiments.record import Record
        name = f"{self.arch}.{self.shape}.{self.mesh}"
        base = {"bottleneck": self.bottleneck, "n_chips": self.n_chips}
        return [
            Record("roofline.table", name, "roofline_fraction",
                   self.roofline_fraction,
                   params=dict(base, compute_s=self.compute_s,
                               memory_s=self.memory_s,
                               collective_s=self.collective_s,
                               useful_ratio=self.useful_ratio)),
            Record("roofline.table", name, "step_s", self.step_s, unit="s",
                   params=base),
        ]


def analyze(cfg: ArchConfig, shape: ShapeConfig, mesh_name: str,
            n_chips: int, compiled, *, device_kind: str,
            pod_size: int = 256) -> CellRoofline:
    from repro.analysis import hlocost
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    costs = hlocost.analyze_text(text, pod_size=pod_size)
    # trip-count-aware totals (xla's cost_analysis counts while bodies once)
    flops = costs.flops
    # memory term: analytic model (the HLO-parsed figure is an unfused
    # XLA:CPU upper bound — reported in `hbm_bytes_upper_bound`)
    n_model = 16
    bytes_acc = analytic_memory_bytes(cfg, shape, n_chips, n_model)
    summ = costs.summary()

    chip = peaks(device_kind)
    compute_s = flops / chip.bf16_flops
    memory_s = bytes_acc / chip.hbm_bytes_per_s
    collective_s = (summ.ici_wire_bytes / chip.ici_bytes_per_s
                    + summ.dcn_wire_bytes / chip.dcn_bytes_per_s)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    mf = model_flops(cfg, shape)
    hlo_global = flops * n_chips
    return CellRoofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, n_chips=n_chips,
        flops_per_device=flops, bytes_per_device=bytes_acc,
        wire_ici=summ.ici_wire_bytes, wire_dcn=summ.dcn_wire_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=max(terms, key=terms.get),
        model_flops=mf, hlo_flops_global=hlo_global,
        useful_ratio=mf / hlo_global if hlo_global else 0.0,
        peak_memory_bytes=float(ma.peak_memory_in_bytes),
        argument_bytes=float(ma.argument_size_in_bytes),
        device_kind=device_kind,
        collectives=dict(summ.to_dict(),
                         hbm_bytes_upper_bound=costs.hbm_bytes),
    )
