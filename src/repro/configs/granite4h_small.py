"""Granite-4.0-H-Small (32B-A9B): a Mamba-2 / attention hybrid whose every
layer ends in a dropless mixture of experts plus a shared expert
[hf:ibm-granite/granite-4.0-h-small, config.json, ``granitemoehybrid``].

``CONFIG`` is the published model: 40 layers, attention at positions 5, 15,
25 and 35 (9 Mamba-2 mixers to 1 attention mixer per 10 layers); Mamba-2
with 128 heads of 64, state 128, 1 group, conv 4 with bias, chunk 256,
expand 2; GQA with 32 query and 8 KV heads of 128 and no positional
encoding; 72 experts of width 768, top 10, SwiGLU, one shared expert of
width 1536; hidden 4096, vocabulary 100,352, tied embeddings, RMSNorm eps
1e-5; multipliers: embedding 12, residual 0.22, attention 1/128, logits
1/16. ``intermediate_size`` (768) is read as the width of one expert, an
inference the config does not state.

``SHARE`` is what one chip holds in a stated deployment of 32 chips: 4
pipeline stages of 10 layers, and in each stage 8 chips that share every
layer by expert parallelism. This chip is one of the first stage's: layers
0-9 (one whole period), experts 0-8 of every layer's 72, and 12,544 (an
eighth) of the vocabulary rows; the mixers, router, shared expert and norms
whole. Its router still routes over all 72 experts with top 10; the chip
computes its own experts' part of each token's output, and that partial
result goes on to the next layer. Parameters are held in bfloat16.
"""
import dataclasses

from repro.configs.base import ModelConfig

PATTERN = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-small",
    family="hybrid_moe",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    norm_eps=1e-5,
    tie_embeddings=True,
    n_experts=72,
    top_k=10,
    shared_d_ff=1536,
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_conv=4,
    ssm_ngroups=1,
    layer_pattern=PATTERN,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
    use_rope=False,
    param_dtype="bfloat16",
    source="hf:ibm-granite/granite-4.0-h-small config.json",
)

SHARE = dataclasses.replace(
    CONFIG,
    arch_id="granite-4.0-h-small-ep8",
    n_layers=10,
    layer_pattern=PATTERN[:10],
    experts_held=tuple(range(9)),
    vocab_size=12544,
)


def reduced() -> ModelConfig:
    """CPU size: both mixer kinds, a run of two Mamba-2 layers, and fewer
    experts held (2) than the router routes over (8, top 3)."""
    return dataclasses.replace(
        SHARE, n_layers=4, layer_pattern=("mamba", "mamba", "attention", "mamba"),
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, shared_d_ff=48,
        vocab_size=512, ssm_state=16, ssm_headdim=16, ssm_chunk=8,
        n_experts=8, top_k=3, experts_held=(0, 1),
    )
