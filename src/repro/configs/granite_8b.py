"""Granite-8B-Code — llama-architecture dense GQA decoder [arXiv:2405.04324; hf]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-8b",
    family="dense",
    num_layers=36,
    d_model=4_096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=14_336,
    vocab_size=49_152,
    head_dim=128,
    rope_theta=10_000_000.0,
    tie_embeddings=True,
)

# One TPU v5e chip's share of granite-8b served as a four-stage pipeline
# over four chips: every width above as published, depth cut
# ``num_layers`` 36 -> 9 (this chip's stage; the other 27 layers would
# lie on the next three chips). 4.33 GB of bf16 weights, random from a
# fixed seed (no checkpoint). Serves at the `full` shapes: prefill
# (1, 2048), decode (8, 2048), encode (32, 512).
CHIP = CONFIG.replace(name="granite-8b-9l", num_layers=9)

SMOKE = CONFIG.replace(
    name="granite-8b-smoke",
    num_layers=2,
    d_model=128,
    num_heads=4,
    num_kv_heads=2,
    head_dim=32,
    d_ff=256,
    vocab_size=512,
)
