"""Mamba2-1.3B — SSD (state-space duality), attention-free [arXiv:2405.21060].

Widths from the state-spaces/mamba2-1.3b config (d_model 2048, n_layer 48,
tied embeddings, vocabulary padded to a multiple of 16) and the Mamba-2
layer's defaults in the paper's code (d_state 128, headdim 64, expand 2,
ngroups 1, d_conv 4, chunk 256). ``vocab_size`` 50280 is assumed: the
published config gives 50277, which pads to the same 50288 rows.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="mamba2-1.3b",
    family="ssm",
    n_layers=48,
    d_model=2048,
    n_heads=0,
    n_kv_heads=0,
    d_ff=0,
    vocab_size=50280,
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_conv=4,
    ssm_ngroups=1,
    tie_embeddings=True,
    source="arXiv:2405.21060; hf:state-spaces/mamba2-1.3b config",
)


def reduced() -> ModelConfig:
    import dataclasses

    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=64, vocab_size=512, ssm_state=16,
        ssm_headdim=16, ssm_chunk=8,
    )
