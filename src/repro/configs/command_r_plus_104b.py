"""Command R+ 104B (Cohere): GQA kv=8, a parallel block under one bias-free
LayerNorm, per-head QK LayerNorm, interleaved RoPE, and a tied 256k head
scaled by ``logit_scale``.
[hf:CohereForAI/c4ai-command-r-plus config.json]"""
from . import ModelConfig

CONFIG = ModelConfig(
    name="command-r-plus-104b", family="dense",
    n_layers=64, d_model=12288, n_heads=96, n_kv_heads=8,
    d_ff=33792, vocab=256000,
    mlp="gated", norm="ln_nobias", pos="rope", rope_theta=75e6,
    rope_interleaved=True, qk_norm="per_head", parallel_block=True,
    tie_embeddings=True, logit_scale=0.8333333333333334,
)
