"""lfm2-8b-a1b [hybrid moe] — LiquidAI LFM2-8B-A1B at its published size:
18 gated short-convolution and 6 GQA attention layers, the first two
with a dense SwiGLU MLP, every later one a mixture of 32 experts (4 per
token, sigmoid router with a selection bias, no shared expert).
[hf:LiquidAI/LFM2-8B-A1B config.json]
"""
from repro.configs.base import ModelConfig

#: ``layer_types`` of the published config ('full_attention' -> 'attn')
LAYER_TYPES = ("conv", "conv", "attn", "conv", "conv", "conv", "attn", "conv",
               "conv", "conv", "attn", "conv", "conv", "conv", "attn", "conv",
               "conv", "conv", "attn", "conv", "conv", "attn", "conv", "conv")
NUM_DENSE_LAYERS = 2

CONFIG = ModelConfig(
    name="lfm2-8b-a1b",
    family="hybrid",
    num_layers=24,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=7168,
    vocab_size=65536,
    rope_theta=1_000_000.0,
    qk_norm=True,
    # the 24 layers are one group: the pattern has no period
    block_pattern=LAYER_TYPES,
    moe_pattern=tuple(range(NUM_DENSE_LAYERS, len(LAYER_TYPES))),
    moe=True,
    num_experts=32,
    top_k=4,
    moe_d_ff=1792,
    router_aux_coef=0.0,
    router_score="sigmoid",
    router_bias=True,
    norm_topk_prob=True,
    routed_scaling_factor=1.0,
    moe_impl="dropless",
    conv_kernel=3,
    rms_norm_eps=1e-5,
    tie_embeddings=True,
    source="hf:LiquidAI/LFM2-8B-A1B (config.json)",
)
