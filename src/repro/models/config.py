"""Unified model configuration covering all assigned architecture families.

One ``ModelConfig`` describes dense / MoE / SSM / hybrid / encoder-decoder /
VLM transformer backbones.  ``src/repro/configs/<arch>.py`` instantiates the
exact published configurations; every arch also exposes a reduced ``smoke()``
variant for CPU tests.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # attention
    rope_theta: float = 10_000.0
    use_rope: bool = True
    qkv_bias: bool = False
    sliding_window: int = 0        # 0 = global attention
    global_every: int = 0          # hybrid: every k-th layer is global
    # normalization / mlp
    norm: str = "rms"              # rms | ln
    norm_eps: float = 1e-5
    act: str = "silu"              # silu | gelu
    mlp_gated: bool = True
    tie_embeddings: bool = False
    # positional fallback when use_rope=False
    max_position: int = 32_768
    # YaRN RoPE scaling (flat fields keep the config hashable for jit);
    # rope_factor 1 leaves RoPE unscaled
    rope_factor: float = 1.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # multi-head latent attention (DeepSeek-V2 MLA, no q compression);
    # kv_lora_rank 0 = standard attention.  Each token caches one row of
    # kv_lora_rank + qk_rope_head_dim values per layer (the latent kind
    # of KV), shared by every head.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    n_shared_experts: int = 0
    n_dense_layers: int = 0        # leading dense layers (e.g. kimi-k2)
    moe_dense_residual: bool = False  # parallel dense MLP (arctic)
    router_scale: float = 1.0      # routed scaling factor on the gates
    router_renorm: bool = True     # renormalise the top-k gates to sum 1
    # expert parallelism: the router's published width, of which this
    # model holds experts [first_expert, first_expert + n_experts);
    # 0 = the router is n_experts wide (every expert held)
    n_routed_experts: int = 0
    first_expert: int = 0

    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_heads: int = 0
    d_ssm_head: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 64

    # encoder-decoder / multimodal frontend
    enc_layers: int = 0
    frontend: str = ""             # "" | audio | image
    frontend_seq: int = 0          # stub frames / patches

    # dtypes
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    kv_dtype: str = ""             # KV-cache storage ("" = compute dtype)

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def kv_kind(self) -> str:
        """What the KV pool holds per token and layer: "kv" (K and V per
        KV head) or "latent" (one MLA row shared by every head)."""
        return "latent" if self.is_mla else "kv"

    @property
    def latent_width(self) -> int:
        """Values of one cached MLA row: the normed latent, then the
        shared RoPE key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def router_width(self) -> int:
        return self.n_routed_experts or self.n_experts

    @property
    def rope_dim(self) -> int:
        """Width RoPE rotates: MLA's RoPE part, else the whole head."""
        return self.qk_rope_head_dim if self.is_mla else self.d_head

    def yarn_magnitude(self, m: float) -> float:
        """YaRN's magnitude factor ``0.1 m ln(rope_factor) + 1`` (1
        without scaling).  The softmax scale carries its square at
        ``rope_mscale_all_dim``; cos and sin carry its ratio at
        ``rope_mscale`` over ``rope_mscale_all_dim``."""
        if self.rope_factor <= 1.0 or not m:
            return 1.0
        return 0.1 * m * math.log(self.rope_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        dq = self.qk_nope_head_dim + self.qk_rope_head_dim if self.is_mla \
            else self.d_head
        return dq ** -0.5 * self.yarn_magnitude(self.rope_mscale_all_dim) ** 2

    @property
    def d_inner_ssm(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def has_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def kvdtype(self):
        return jnp.dtype(self.kv_dtype or self.compute_dtype)

    def n_params(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, f, V = self.d_model, self.d_ff, self.vocab
        emb = V * d * (1 if self.tie_embeddings else 2)
        if not self.use_rope and self.family == "encdec":
            emb += self.max_position * d  # learned positions
        per_attn = d * (self.n_heads + 2 * self.n_kv_heads) * self.d_head \
            + self.n_heads * self.d_head * d
        if self.is_mla:
            H, r = self.n_heads, self.kv_lora_rank
            nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                              self.v_head_dim)
            per_attn = d * H * (nope + rope) + d * (r + rope) + r \
                + r * H * (nope + dv) + H * dv * d
        per_mlp = d * f * (3 if self.mlp_gated else 2)
        per_moe = 0
        if self.is_moe:
            e = self.d_expert or f
            per_moe = (d * self.router_width
                       + self.n_experts * d * e * 3
                       + self.n_shared_experts * d * e * 3)
        per_ssm = 0
        if self.has_ssm:
            di = self.d_inner_ssm
            ns = self.ssm_heads
            per_ssm = d * 2 * di + di * d + d * (2 * self.ssm_state) \
                + di * self.ssm_conv + 2 * ns + di
        blocks = 0
        for li in range(self.n_layers):
            blocks += per_attn if self.has_attention else 0
            blocks += per_ssm if self.has_ssm else 0
            if self.is_moe and li >= self.n_dense_layers:
                blocks += per_moe + (per_mlp if self.moe_dense_residual else 0)
            else:
                blocks += per_mlp if f else 0
        enc = 0
        if self.enc_layers:
            enc = self.enc_layers * (per_attn + per_mlp) \
                + self.n_layers * per_attn  # decoder cross-attention
        return emb + blocks + enc

    def n_active_params(self) -> int:
        """Per-token active parameters (MoE: top_k + shared experts only)."""
        if not self.is_moe:
            return self.n_params()
        d = self.d_model
        e = self.d_expert or self.d_ff
        inactive = max(self.n_experts - self.top_k, 0) * d * e * 3 \
            * (self.n_layers - self.n_dense_layers)
        return self.n_params() - inactive


# shape cells assigned to every LM-family architecture
@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES: Tuple[ShapeCell, ...] = (
    ShapeCell("train_4k", 4_096, 256, "train"),
    ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    ShapeCell("decode_32k", 32_768, 128, "decode"),
    ShapeCell("long_500k", 524_288, 1, "decode"),
)


def shape_by_name(name: str) -> ShapeCell:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


def cell_applicable(cfg: ModelConfig, cell: ShapeCell) -> tuple[bool, str]:
    """long_500k needs sub-quadratic attention: run only for SSM/hybrid
    (sliding-window or state-space) families; full-attention archs skip."""
    if cell.name == "long_500k" and not (
            cfg.family in ("ssm", "hybrid")):
        return False, "full attention at 524k context out of scope (per spec)"
    return True, ""
