"""Operations and bytes of the device work, counted from shapes.

``mamba2_flops_per_token``: the forward FLOPs per token of a Mamba-2 LM
(multiply-add = 2 FLOPs), as the chunked SSD algorithm computes it at chunk
length ``chunk``:

- input projection ``d -> 2*di + 2*g*ds + nh``: ``2 * d * proj``;
- depthwise causal conv over ``di + 2*g*ds`` channels, kernel ``k``:
  ``2 * conv_dim * k``;
- SSD, per token: ``C B^T`` inside the chunk, once per group
  (``2 * chunk * ds * g``); that matrix times ``x`` (``2 * chunk * hd * nh``);
  the chunk state ``B^T x`` (``2 * ds * hd * nh``); the state's output
  ``C h`` (``2 * ds * hd * nh``);
- output projection ``di -> d``: ``2 * di * d``;
- the tied LM head ``d -> vocab``: ``2 * d * vocab``.

Norms, activations and the inter-chunk recurrence are elementwise and left
out. ``minhash_kernel_bytes``: the HBM bytes one MinHash kernel call must
move, read and written once: uint32 hashes and bool mask ``(D, S)``, uint32
``a`` and ``b`` ``(P,)`` in, uint32 signatures ``(D, P)`` out.
"""
from __future__ import annotations

from typing import Any, Dict


def mamba2_flops_per_token(cfg: Dict[str, Any], chunk: int) -> int:
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    hd = cfg["ssm_headdim"]
    nh = di // hd
    ds = cfg["ssm_state"]
    g = cfg["ssm_ngroups"]
    proj = 2 * di + 2 * g * ds + nh
    conv_dim = di + 2 * g * ds
    per_layer = (2 * d * proj + 2 * conv_dim * cfg["ssm_conv"]
                 + 2 * chunk * ds * g + 2 * chunk * hd * nh + 4 * ds * hd * nh
                 + 2 * di * d)
    return cfg["n_layers"] * per_layer + 2 * d * cfg["vocab_size"]


def minhash_kernel_bytes(d: int, s: int, p: int) -> int:
    return d * s * 4 + d * s * 1 + 2 * p * 4 + d * p * 4

