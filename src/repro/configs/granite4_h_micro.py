"""granite-4.0-h-micro [hybrid]: 40L d=2048, 36 Mamba-2 + 4 NoPE GQA layers.

[hf:ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid] — attention at layers 5, 15, 25, 35 (one period of 10:
nine Mamba-2 layers and one attention layer at index 5); every layer has a
SwiGLU MLP of width 8,192. Mamba-2: 64 heads x 64, d_state 128, one
group, conv width 4 with bias, chunk 256. Attention: 32 query and 8 KV
heads of 64 (head_dim is null in the config: 2,048 / 32), no position
embedding. muP constants: embeddings x12, each residual branch x0.22,
softmax scale 1/64, logits / 8. RMSNorm eps 1e-5, the gated norm's too.
Vocabulary 100,352 rows tied to the embedding; weights in bfloat16, as
released.

``ONE_PERIOD`` is one whole period of the pattern (10 layers, attention at
index 5), the one-chip benchmark's cut.
"""
from dataclasses import replace

from .base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite4_h_micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    attn_every=10,
    attn_offset=5,
    ssm=SSMConfig(n_heads=64, head_dim=64, d_state=128, n_groups=1, conv_width=4,
                  chunk=256),
    rope_pct=0.0,  # position_embedding_type "nope"
    tie_embeddings=True,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_multiplier=0.015625,
    ssm_conv_bias=True,
    param_dtype="bfloat16",
)

ONE_PERIOD = replace(CONFIG, name="granite4_h_micro_one_period", n_layers=10)

SMOKE = replace(
    CONFIG,
    name="granite4_h_micro_smoke",
    n_layers=10,  # one period: attention at index 5, as published
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab_size=512,
    ssm=SSMConfig(n_heads=8, head_dim=16, d_state=16, n_groups=1, conv_width=4,
                  chunk=16),
    attn_chunk=16,
)
