"""Model/config schema shared by every architecture.

One frozen dataclass covers all six families (dense / moe / ssm / hybrid /
audio / vlm); family-specific fields are zero/empty when unused. Configs are
data — models are built from them by ``repro.models.transformer.build_model``.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    vocab_size: int
    # attention (0s for attention-free families)
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    qkv_bias: bool = False
    rope: str = "full"                # full | half | none
    rope_theta: float = 10_000.0
    window: Optional[int] = None      # sliding-window size (SWA layers)
    global_layers: Tuple[int, ...] = ()   # layer ids with full attention
    attn_logit_softcap: float = 0.0
    # mlp
    d_ff: int = 0
    mlp: str = "swiglu"               # swiglu | gelu | sqrelu
    norm: str = "rms"                 # rms | ln
    tie_embeddings: bool = False
    # MoE
    num_experts: int = 0
    experts_top_k: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0
    router_aux_coef: float = 0.001
    moe_capacity_factor: float = 1.25
    moe_block_tokens: int = 4096      # token block for blocked dispatch
    # MLA (deepseek)
    mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # SSM (mamba-1)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 0
    # hybrid (hymba): parallel attn+ssm heads in every layer
    hybrid: bool = False
    # audio (musicgen): decoder over EnCodec codebooks
    num_codebooks: int = 0
    # vlm (internvl): precomputed patch embeddings prepended to text
    num_patches: int = 0
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # training-memory policy
    remat: bool = True
    loss_chunk: int = 2048            # tokens per chunked-CE step
    # attention memory optimizations (§Perf hillclimb; off = paper-period
    # baseline): fold the softmax scale into q (one fewer score-sized
    # materialization) and keep the exp/probs chain in bf16 (f32 stats).
    attn_scale_in_q: bool = False
    attn_probs_bf16: bool = False
    # dry-run cost accounting: unroll every inner scan so cost_analysis sees
    # the full op count (XLA does not multiply while bodies by trip count).
    # Used only by depth-variant compiles; never for the full-depth model.
    unroll: bool = False

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def uses_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch decode at 500k context? (SSM state or windowed attn)"""
        return self.family in ("ssm", "hybrid")

    def scaled(self, **overrides) -> "ModelConfig":
        """A reduced copy for smoke tests (same family/topology, tiny dims)."""
        return replace(self, **overrides)

    def with_depth(self, num_layers: int) -> "ModelConfig":
        """Same widths, ``num_layers`` deep. Global-attention layers stay at
        the first, evenly spaced and last positions, as many as fit."""
        if not self.global_layers:
            return replace(self, num_layers=num_layers)
        n, last = len(self.global_layers), num_layers - 1
        pos = [0] if n == 1 else [i * last // (n - 1) for i in range(n)]
        return replace(self, num_layers=num_layers,
                       global_layers=tuple(sorted(set(pos))))


@dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell: what gets lowered and with which step."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k":    ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k":   ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Is this (arch x shape) cell runnable? (DESIGN.md §Arch-applicability)"""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("full-attention arch: 524k-context decode is "
                       "quadratic-cost; run only for ssm/hybrid")
    return True, ""
