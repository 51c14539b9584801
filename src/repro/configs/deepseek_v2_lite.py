"""deepseek-v2-lite [moe] — multi-head latent attention (no q
compression) over a latent KV cache, 64 routed experts top-6 with 2
shared experts, the first layer dense, YaRN RoPE.

27L d_model=2048 16H kv_lora_rank=512 qk_nope=128 qk_rope=64 v=128
d_ff=10944 d_expert=1408 vocab=102400
[hf:deepseek-ai/DeepSeek-V2-Lite]

``CONFIG`` is the published model, every expert held (15.7B
parameters).  ``expert_share(cfg, chips, chip)`` is one chip's share of
an expert-parallel deployment: the router keeps its 64 outputs, the chip
holds 64 / chips experts of each MoE layer (``bench/configs/
deepseek_v2_lite.json`` serves chip 0 of 8: 3.11B parameters).
"""
import dataclasses

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab=102400, rope_theta=10_000.0, norm_eps=1e-6,
    rope_factor=40.0, rope_original_max_position=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale=0.707, rope_mscale_all_dim=0.707,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, d_expert=1408, n_shared_experts=2,
    n_dense_layers=1, router_renorm=False, router_scale=1.0,
    max_position=163840,
)


def expert_share(cfg: ModelConfig, chips: int, chip: int) -> ModelConfig:
    """``cfg`` as chip ``chip`` of ``chips`` holds it under expert
    parallelism: a contiguous ``1 / chips`` of the routed experts, the
    router at its full width; everything else replicated."""
    width = cfg.router_width
    assert width % chips == 0 and 0 <= chip < chips, (width, chips, chip)
    held = width // chips
    return dataclasses.replace(cfg, n_experts=held, n_routed_experts=width,
                               first_expert=chip * held)


def smoke():
    return dataclasses.replace(
        CONFIG, name="deepseek-v2-lite-smoke", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=96, vocab=256, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_experts=16, top_k=4, d_expert=32, n_shared_experts=2,
        max_position=4096)
