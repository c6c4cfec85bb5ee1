"""Roofline-term derivation from compiled XLA artifacts (DESIGN.md §8).

This container is CPU-only (TPU v5e is the TARGET, not the runtime), so
wall-time cannot be measured; instead every (arch x shape x mesh) dry-run
yields the three roofline terms from its compiled module:

  compute term    = per-device HLO FLOPs / peak_FLOP/s      [s]
  memory term     = per-device HLO bytes / HBM_bw           [s]
  collective term = per-device collective bytes / link_bw   [s]

cost_analysis() is PER-DEVICE after SPMD partitioning (verified
empirically), matching the instructions' HLO_FLOPs/(chips x peak) with
HLO_FLOPs summed over chips. Collective bytes are NOT in cost_analysis:
they are parsed from the optimized HLO text by summing the result-shape
bytes of every collective op (payload ~ bytes leaving/entering a device).
"""
from __future__ import annotations

import re
from typing import NamedTuple

# TPU v5e hardware constants (per chip), from the assignment.
PEAK_FLOPS = 197e12  # bf16 FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"= (.+?) (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(pred|[subf]\d+|bf16|c\d+)\[([\d,]*)\]")


def _shape_bytes(spec: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(spec):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind payload bytes (result shapes), per device."""
    out: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        result_spec, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0) + _shape_bytes(result_spec)
    return out


class RooflineTerms(NamedTuple):
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_breakdown: dict[str, int]
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def total_s(self) -> float:
        # no-overlap upper bound; perfect overlap would be max() instead
        return self.compute_s + self.memory_s + self.collective_s


def roofline(compiled) -> RooflineTerms:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    breakdown = collective_bytes(compiled.as_text())
    cb = float(sum(breakdown.values()))
    return RooflineTerms(
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_bytes_per_device=cb,
        collective_breakdown=breakdown,
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=cb / ICI_BW,
    )


def model_flops_train(cfg, seq_len: int, global_batch: int) -> float:
    """MODEL_FLOPS = 6 N D with N = active params (MoE: routed active only),
    D = tokens. Per the assignment's definition for the 'useful compute'
    ratio (train includes fwd+bwd: the 6x already accounts for it)."""
    n_active = active_param_count(cfg)
    return 6.0 * n_active * seq_len * global_batch


def model_flops_decode(cfg, global_batch: int) -> float:
    """One decoded token per sequence: 2 N D (fwd only)."""
    return 2.0 * active_param_count(cfg) * global_batch


def has_time_while_loops(cfg) -> bool:
    """True if any block runs a lax.scan over TIME (mlstm chunk scan, slstm
    step scan) — their in-loop cost is invisible to cost_analysis, so the
    dry-run swaps in the analytical count below for the compute term."""
    return any(b in ("mlstm", "slstm") for b in cfg.block_pattern)


def analytical_flops_recurrent(cfg, seq_len: int, batch: int, kind: str, chunk: int = 64) -> float:
    """TOTAL (all-device) flops for mlstm/slstm architectures, matmul-level
    accounting of exactly what repro.models.ssm computes.

    Train counts fwd x 4 (backward 2x + remat recompute 1x, matching
    cfg.remat=True); prefill counts fwd; decode counts the one-step path.
    """
    D, V = cfg.d_model, cfg.vocab_size
    H = cfg.num_heads
    inner = cfg.rnn_width or 2 * D
    dh = inner // H
    W = cfg.rnn_width or D

    def mlstm_tok(decode: bool) -> float:
        proj = 2 * D * inner * 2 + 3 * 2 * inner * inner + 2 * inner * 2 * H + 2 * inner * D
        conv = 2 * cfg.conv_width * inner
        if decode:
            rec = H * (6 * dh * dh + 6 * dh)  # kv outer + state read + norms
        else:
            # per-chunk: scores 2c^2 dh, intra-out 2c^2 dh, decay ~4c^2,
            # inter q@C 2c dh^2, state update 2c dh^2  => per token:
            rec = H * (4 * chunk * dh + 4 * dh * dh + 4 * chunk)
        return proj + conv + rec

    def slstm_tok(decode: bool) -> float:
        return 2 * D * 4 * W + 2 * W * 4 * W + 24 * W + 2 * W * D

    per_tok = 0.0
    for i in range(cfg.num_layers):
        kind_i = cfg.block_pattern[i % cfg.period]
        if kind_i == "mlstm":
            per_tok += mlstm_tok(kind == "decode")
        elif kind_i == "slstm":
            per_tok += slstm_tok(kind == "decode")
    per_tok += 2 * D * V  # lm head
    tokens = batch * (1 if kind == "decode" else seq_len)
    fwd = per_tok * tokens
    if kind == "train":
        return 4.0 * fwd
    return fwd


def active_param_count(cfg) -> float:
    """Active (per-token) parameter count from the config — non-embedding
    blocks + embeddings; MoE counts top_k + shared experts only."""
    D, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    total = V * D * (1 if cfg.tie_embeddings else 2)  # embed + head
    per_pattern = {}
    for kind in set(cfg.block_pattern):
        if kind in ("attn", "local_attn"):
            p = D * H * hd + 2 * D * KV * hd + H * hd * D
        elif kind == "mla":
            a = cfg.mla
            qd = a.qk_nope_head_dim + a.qk_rope_head_dim
            p = (D * a.q_lora_rank + a.q_lora_rank * H * qd + D * a.kv_lora_rank
                 + D * a.qk_rope_head_dim + a.kv_lora_rank * H * a.qk_nope_head_dim
                 + a.kv_lora_rank * H * a.v_head_dim + H * a.v_head_dim * D)
        elif kind == "mlstm":
            inner = cfg.rnn_width or 2 * D
            p = 2 * D * inner + 3 * inner * inner + inner * 2 * H + inner * D
        elif kind == "slstm":
            W = cfg.rnn_width or D
            p = D * 4 * W + W * 4 * W + W * D
        elif kind == "rglru":
            W = cfg.rnn_width or D
            p = 2 * D * W + 2 * W * W + W * D
        else:
            p = 0
        per_pattern[kind] = p
    # mixing blocks, layer by layer (pattern cycled)
    for i in range(L):
        total += per_pattern[cfg.block_pattern[i % cfg.period]]
    # FFN per layer
    if cfg.mlp_kind != "none":
        if cfg.moe is not None:
            m = cfg.moe
            active_ff = (m.top_k + m.num_shared) * m.d_expert
            per_moe = 3 * D * active_ff + D * m.num_experts  # + router
            n_moe = L - (1 if m.first_layer_dense else 0)
            total += n_moe * per_moe
            if m.first_layer_dense:
                total += 3 * D * m.dense_d_ff
        else:
            mult = 3 if cfg.mlp_kind == "swiglu" else 2
            total += L * mult * D * cfg.d_ff
    # encoder stack (whisper)
    if cfg.encoder is not None:
        e = cfg.encoder
        enc_per = D * H * hd + 2 * D * KV * hd + H * hd * D + 2 * D * cfg.d_ff
        total += e.num_layers * enc_per
        # decoder cross-attention
        total += L * (D * H * hd + 2 * D * KV * hd + H * hd * D)
    return float(total)
