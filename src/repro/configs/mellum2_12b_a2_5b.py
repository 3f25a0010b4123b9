"""Mellum2-12B-A2.5B — sliding-window and full GQA attention in a period
of four layers, 64 SwiGLU experts top-8 in every layer, no shared
expert; one chip's share of a four-chip deployment.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct; hf]"""
from .base import ModelConfig, Yarn

# published layer_types: [sliding, sliding, sliding, full] seven times
LAYER_TYPES = tuple("attention" if i % 4 == 3 else "sliding_attention"
                    for i in range(28))

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv=4, d_head=128, d_ff=7168,
    vocab=98304, n_experts=64, top_k=8, d_expert=896,
    experts_held=16, expert_lo=0,
    layer_types=LAYER_TYPES, window=1024, rope_theta=500000.0,
    yarn=Yarn(factor=16.0, original_max=8192, beta_fast=32.0, beta_slow=1.0,
              attention_factor=1.2772588722239782),
    norm_eps=1e-6, tie_embeddings=False, act="swiglu", norm="rms",
    notes="published 28 layers ([sliding 1024, sliding, sliding, full] x 7; "
          "plain RoPE on sliding, YaRN x16 from 8192 on full, theta 5e5), "
          "GQA 32/4 x 128, 64 experts of 896 top-8 (norm_topk_prob) in "
          "every layer, untied head of 98,304; served here: all 28 layers "
          "and experts 0-15, the share of one chip of a four-chip host "
          "(4-way expert parallelism); router over all 64")
