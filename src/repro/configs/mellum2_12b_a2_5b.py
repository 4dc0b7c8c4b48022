"""mellum2-12b-a2.5b [moe]  [hf:JetBrains/Mellum2-12B-A2.5B-Instruct].

28L d_model=2304 32H (GQA kv=4, head_dim 128) vocab=98304, untied.  Layers
in periods of three sliding-window layers (window 1024) and one full
layer; RoPE theta 500000 on both, with YaRN (factor 16 over an original
8192 positions) on the full layers.  Every layer's FFN is MoE: 64 experts
of width 896, softmax routing, top-8 renormalised (``norm_topk_prob``).
Per-head q/k RMSNorm is assumed from the Qwen3-MoE keys of its config.
The MTP head is not modelled.
"""
from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, ModelConfig,
                                MoEConfig, YarnConfig, register)


@register("mellum2-12b-a2.5b")
def config() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        n_layers=28,
        d_model=2304,
        n_heads=32,
        n_kv_heads=4,
        head_dim=128,
        d_ff=7168,
        vocab_size=98_304,
        layer_pattern=(ATTN_LOCAL,) * 3 + (ATTN_GLOBAL,),
        window_size=1024,
        rope_theta=500_000.0,
        rope_yarn=YarnConfig(factor=16.0,
                             original_max_position_embeddings=8192,
                             beta_fast=32.0, beta_slow=1.0,
                             attention_factor=1.2772588722239782),
        qk_norm=True,
        act="silu",
        tie_embeddings=False,
        moe=MoEConfig(n_experts=64, experts_per_token=8, d_ff_expert=896),
    )
