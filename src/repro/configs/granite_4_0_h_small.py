"""granite-4.0-h-small — Mamba2/attention hybrid, 72 experts top-10 + a
shared expert in every layer, NoPE; one chip's share of a 16-chip
deployment.  [hf:ibm-granite/granite-4.0-h-small; hf]"""
from .base import ModelConfig

# published layer_types: attention at 5, 15, 25 and 35, Mamba2 elsewhere
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="moe",
    n_layers=10, d_model=4096, n_heads=32, n_kv=8, d_ff=768,
    vocab=100352, n_experts=72, top_k=10, n_shared=1, d_expert=768,
    d_shared=1536, experts_held=18, expert_lo=0,
    layer_types=LAYER_TYPES, rope=False, norm_eps=1e-5,
    ssm_state=128, ssm_heads=128, ssm_head_dim=64, ssm_inner=8192,
    conv_k=4, conv_bias=True, ssd_chunk=256,
    act="swiglu", norm="rms",
    notes="published 40 layers (36 Mamba2 + 4 NoPE GQA 32/8 attention, "
          "period 10) and 72 experts a layer; served here: the first "
          "period (layers 0-9) and experts 0-17, the share of one chip "
          "of a 16-chip deployment (4 pipeline stages x 4-way expert "
          "parallelism); router over all 72, top-10; shared SwiGLU 1536")
