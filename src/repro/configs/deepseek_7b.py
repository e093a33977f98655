"""deepseek-7b [dense]: 30L d=4096 32H (kv=32 i.e. MHA) ff=11008 vocab=102400.

llama-arch [arXiv:2401.02954; hf] — RMSNorm, SwiGLU, full rotary.
"""
from dataclasses import replace

from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek_7b",
    family="dense",
    n_layers=30,
    d_model=4096,
    n_heads=32,
    n_kv_heads=32,
    d_ff=11008,
    vocab_size=102400,
    rope_theta=10000.0,
)

#: One chip's share of the published model, cut in depth only. Every width,
#: the head count, the vocabulary and the float32 weight storage are as in
#: ``CONFIG``. A dense model's layer pattern has period 1, so 4 layers hold
#: every kind of layer it has. Prefill attention runs the Pallas flash kernel.
ONE_CHIP = replace(CONFIG, name="deepseek_7b_1chip", n_layers=4, attn_impl="pallas")

#: How ``ONE_CHIP`` was cut from the published config.
ONE_CHIP_CUT = {
    "source": "deepseek-ai/deepseek-llm-7b-base config.json (DeepSeek LLM, "
              "arXiv:2401.02954): 30 layers, hidden 4096, 32 MHA heads, "
              "intermediate 11008, vocab 102400",
    "reduced": {"n_layers": (30, 4)},
    "deployment": "the 30-layer model served as pipeline stages of 4 layers; "
                  "the other 26 layers would sit on further chips, and this "
                  "chip holds the embedding and the LM head with its stage",
}

SMOKE = ModelConfig(
    name="deepseek_7b_smoke",
    family="dense",
    n_layers=3,
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    d_ff=344,
    vocab_size=512,
    attn_impl="full",
)
