"""Operations of granite-4.0-h-small's step on one chip, counted from shapes
(multiply-add = 2 FLOPs), forward only, from the configuration's ``scorer``
block.

Per token:

- a Mamba-2 layer: ``bench.costs.mamba2_flops_per_token``'s layer terms at
  the configured chunk (in- and out-projection, conv, the chunked SSD);
- an attention layer: the q, k, v and o projections, ``2 * d * (q + 2 kv)
  + 2 * q * d``, and the causal scores and values, ``2 * seq * q`` (each
  position sees ``seq / 2`` others on average, two products of
  ``2 * (seq / 2) * q``);
- every layer's expert block: the router ``2 * d * E``, the shared expert
  ``6 * d * fs``, and the held experts' expected work,
  ``top_k * H / E`` expert FFNs of ``6 * d * f`` (``expert_row_flops``):
  under uniform routing a token sends ``top_k * H / E`` of its assignments
  to the ``H`` experts a chip holds;
- the tied head ``2 * d * vocab``.

Norms, activations, routing's sort and gathers are left out.
"""
from __future__ import annotations

from typing import Any, Dict

from bench.costs import mamba2_flops_per_token


def expert_row_flops(sc: Dict[str, Any]) -> int:
    """One assignment through one expert: gate and up ``d -> 2f``, down
    ``f -> d``."""
    return 6 * sc["hidden_size"] * sc["intermediate_size"]


def flops_per_token(sc: Dict[str, Any]) -> int:
    d = sc["hidden_size"]
    q = sc["num_attention_heads"] * (d // sc["num_attention_heads"])
    kv = sc["num_key_value_heads"] * (d // sc["num_attention_heads"])
    mamba = mamba2_flops_per_token(
        {"d_model": d, "ssm_expand": sc["mamba_expand"], "ssm_headdim": sc["mamba_d_head"],
         "ssm_state": sc["mamba_d_state"], "ssm_ngroups": sc["mamba_n_groups"],
         "ssm_conv": sc["mamba_d_conv"], "n_layers": 1, "vocab_size": 0},
        sc["mamba_chunk_size"])
    attention = 2 * d * (q + 2 * kv) + 2 * q * d + 2 * sc["seq_len"] * q
    e, k, h = sc["router_experts"], sc["num_experts_per_tok"], len(sc["experts_held"])
    experts = (2 * d * e + 6 * d * sc["shared_intermediate_size"]
               + k * h * expert_row_flops(sc) // e)
    kinds = sc["layer_types"]
    return (kinds.count("mamba") * mamba + kinds.count("attention") * attention
            + len(kinds) * experts + 2 * d * sc["vocab_size"])
