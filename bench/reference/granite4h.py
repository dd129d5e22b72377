"""Plain reference of the scorer granite-4.0-h-small (one chip's share of an
expert group): each row's mean next-token loss, in float32 ``jax.numpy`` at
the highest matmul precision.

Written from the architecture (the published ``config.json`` of
ibm-granite/granite-4.0-h-small, ``granitemoehybrid``) as the configuration
file's ``scorer`` block states it, with none of the program's code:

- tokens: the hashed-word tokenizer of ``bench.reference.mamba2``, into the
  ``vocab_size`` rows this chip holds;
- ``x = E[tokens] * embedding_multiplier``, ``E`` tied to the head;
- each layer ``i`` of ``layer_types``, pre-norm, residuals scaled by
  ``r = residual_multiplier``: ``x = x + r * mixer(rmsnorm(x) * norm)``,
  then ``h = rmsnorm(x) * ffn_norm`` and
  ``x = x + r * (experts(h) + shared(h))``;
- the Mamba-2 mixer: ``[z, xBC, dt] = h @ w_in``;
  ``xBC = silu(causal depthwise conv(xBC) + conv_b)``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(a_log)``; per head the state
  ``S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T`` and
  ``y_t = S_t C_t + d_skip * x_t``, one position after another (no chunked
  scan); ``out = rmsnorm(y * silu(z)) * norm_g @ w_out``;
- the attention mixer: causal, ``num_attention_heads`` query heads sharing
  ``num_key_value_heads`` key-value heads of ``hidden_size / heads``, scores
  ``q k^T * attention_multiplier``, no positional encoding;
- ``experts(h)``: the router's logits ``h @ router`` over all
  ``router_experts``, their top ``num_experts_per_tok`` and a softmax over
  those; then for each held expert ``e`` (``experts_held``), its gate where
  chosen (else 0) times ``(silu(h @ g_e) * (h @ u_e)) @ w_down_e``, summed:
  a plain sum per token, every held expert on every token, nothing dropped
  or renormalised. ``shared`` is the same FFN with the shared weights;
- a final ``rmsnorm`` with weight ``g``, logits ``x @ E^T / logits_scaling``.

Weights, as the configuration's ``weights`` states: ``E`` is
``N(0, 0.02^2)`` from ``fold_in(PRNGKey(0), 0)``; layer ``i``'s leaves, in
sorted name order, from ``split(fold_in(PRNGKey(0), i + 1), n_leaves)``;
held expert ``e`` of ``w_gate_up`` (gate columns, then up) and ``w_down``
from ``fold_in(leaf_key, e)``; ``N(0, 1/fan_in)`` for matrices (``conv_w``'s
fan-in is its channels), ones for the layers' norms and ``d_skip``, zeros
for ``conv_b``, ``dt_bias`` and ``a_log``; the final norm's weight ``g =
logits_scaling / (0.02 * sqrt(hidden_size))``, at which a unit-RMS state
gives logits of unit spread against ``E`` (with ones they would spread
0.08 nats at the published widths, and no row's loss could tell what the
layers computed).

``quantize`` makes the control: every projection's operands, the router's,
the experts', the head's and the embedding table, rounded to the given type
under one scale per tensor before a float32 product.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import numpy as np

from bench.costs_granite4h import flops_per_token  # noqa: F401  (interface)
from bench.reference.mamba2 import _round, encode, real_tokens  # noqa: F401

# the scorer keys of the program's reduced granite-4.0-h-small-ep8, short
# rows, few checked
TINY = {"layer_types": ["mamba", "mamba", "attention", "mamba"], "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "mamba_d_head": 16,
        "mamba_n_heads": 8, "mamba_d_state": 16, "mamba_chunk_size": 8, "intermediate_size": 32,
        "shared_intermediate_size": 48, "router_experts": 8, "num_experts_per_tok": 3,
        "experts_held": [0, 1], "vocab_size": 512, "seq_len": 16, "rows_checked": 16}

ONES = ("norm", "norm_g", "ffn_norm", "d_skip")
ZEROS = ("conv_b", "dt_bias", "a_log")


def dims(sc: Dict[str, Any]) -> Dict[str, int]:
    d = sc["hidden_size"]
    di = sc["mamba_expand"] * d
    gds = sc["mamba_n_groups"] * sc["mamba_d_state"]
    nh = sc["mamba_n_heads"]
    assert nh * sc["mamba_d_head"] == di, (nh, sc["mamba_d_head"], di)
    return {"d": d, "di": di, "nh": nh, "hd": sc["mamba_d_head"], "ds": sc["mamba_d_state"],
            "g": sc["mamba_n_groups"], "conv_dim": di + 2 * gds, "proj": 2 * di + 2 * gds + nh,
            "k": sc["mamba_d_conv"], "heads": sc["num_attention_heads"],
            "kv_heads": sc["num_key_value_heads"], "head_dim": d // sc["num_attention_heads"],
            "f": sc["intermediate_size"], "fs": sc["shared_intermediate_size"],
            "E": sc["router_experts"], "vocab": sc["vocab_size"]}


def leaf_shapes(sc: Dict[str, Any], kind: str) -> Dict[str, tuple]:
    """One layer's leaves and their shapes; an expert leaf's shape is one
    expert's."""
    s = dims(sc)
    d, f, fs = s["d"], s["f"], s["fs"]
    out = {"ffn_norm": (d,), "router": (d, s["E"]), "w_gate_up": (d, 2 * f), "w_down": (f, d),
           "shared_gate": (d, fs), "shared_up": (d, fs), "shared_down": (fs, d), "norm": (d,)}
    if kind == "mamba":
        out.update(w_in=(d, s["proj"]), conv_w=(s["conv_dim"], s["k"]), conv_b=(s["conv_dim"],),
                   dt_bias=(s["nh"],), a_log=(s["nh"],), d_skip=(s["nh"],), norm_g=(s["di"],),
                   w_out=(s["di"], d))
    else:
        q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
        out.update(wq=(d, q), wk=(d, kv), wv=(d, kv), wo=(q, d))
    return out


def layer_weights(sc: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights, float32, on the default device; an expert leaf
    holds the held experts, stacked in ``experts_held`` order."""
    import jax
    import jax.numpy as jnp

    shapes = leaf_shapes(sc, sc["layer_types"][i])
    names = sorted(shapes)
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0), i + 1), len(names))
    w = {}
    for name, key in zip(names, keys):
        shape = shapes[name]
        std = 1.0 / math.sqrt(shape[-2]) if len(shape) > 1 else 0.0
        if name in ONES:
            w[name] = jnp.ones(shape, jnp.float32)
        elif name in ZEROS:
            w[name] = jnp.zeros(shape, jnp.float32)
        elif name in ("w_gate_up", "w_down"):
            w[name] = jnp.stack([std * jax.random.normal(jax.random.fold_in(key, e), shape)
                                 for e in sc["experts_held"]])
        else:
            w[name] = std * jax.random.normal(key, shape, jnp.float32)
    return w


def embedding(sc: Dict[str, Any]):
    import jax

    s = dims(sc)
    return 0.02 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0), 0),
                                    (s["vocab"], s["d"]))


def final_gain(sc: Dict[str, Any]) -> float:
    return sc["logits_scaling"] / (0.02 * math.sqrt(sc["hidden_size"]))


def init_weights(sc: Dict[str, Any]):
    """All of the configuration's weights: ``{"embed", "final_norm",
    "layers": [layer_weights(sc, i), ...]}``."""
    import jax.numpy as jnp

    return {"embed": embedding(sc),
            "final_norm": jnp.full((sc["hidden_size"],), final_gain(sc), jnp.float32),
            "layers": [layer_weights(sc, i) for i in range(len(sc["layer_types"]))]}


def make_layers(sc: Dict[str, Any], quantize: Optional[str] = None):
    """Jitted ``{"mamba": f, "attention": f}``, ``f(w, x) -> x`` one layer on
    hidden states (B, T, d), and ``head(embed, x, labels, mask) -> (B,)``
    per-row mean loss."""
    import jax
    import jax.numpy as jnp

    s = dims(sc)
    eps = sc["rms_norm_eps"]
    r = sc["residual_multiplier"]
    qt = None if quantize is None else jnp.dtype(quantize)

    def mm(a, b):
        return jnp.matmul(_round(a, qt), _round(b, qt))

    def rmsnorm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g

    def ffn(h, g, u, w):
        return mm(jax.nn.silu(mm(h, g)) * mm(h, u), w)

    def mamba(h, w):
        bsz, T, _ = h.shape
        di, nh, hd, ds, g = s["di"], s["nh"], s["hd"], s["ds"], s["g"]
        zxbcdt = mm(h, w["w_in"])
        z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + s["conv_dim"]],
                      zxbcdt[..., di + s["conv_dim"]:])
        k = s["k"]
        xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(xp[:, i:i + T] * w["conv_w"][:, i] for i in range(k)) + w["conv_b"])
        xs = xbc[..., :di].reshape(bsz, T, nh, hd)
        rep = nh // g
        bm = jnp.repeat(xbc[..., di:di + g * ds].reshape(bsz, T, g, ds), rep, axis=2)
        cm = jnp.repeat(xbc[..., di + g * ds:].reshape(bsz, T, g, ds), rep, axis=2)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        a = -jnp.exp(w["a_log"])

        def step(state, inp):
            x_t, b_t, c_t, dt_t = inp  # (B,nh,hd) (B,nh,ds) (B,nh,ds) (B,nh)
            state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                     + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
            return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

        _, ys = jax.lax.scan(step, jnp.zeros((bsz, nh, hd, ds), jnp.float32),
                             tuple(v.swapaxes(0, 1) for v in (xs, bm, cm, dt)))
        y = ys.swapaxes(0, 1) + w["d_skip"][:, None] * xs
        y = y.reshape(bsz, T, di) * jax.nn.silu(z)
        return mm(rmsnorm(y, w["norm_g"]), w["w_out"])

    def attention(h, w):
        bsz, T, _ = h.shape
        nq, nkv, hd = s["heads"], s["kv_heads"], s["head_dim"]
        q = mm(h, w["wq"]).reshape(bsz, T, nq, hd)
        k = jnp.repeat(mm(h, w["wk"]).reshape(bsz, T, nkv, hd), nq // nkv, axis=2)
        v = jnp.repeat(mm(h, w["wv"]).reshape(bsz, T, nkv, hd), nq // nkv, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sc["attention_multiplier"]
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return mm(jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(bsz, T, nq * hd), w["wo"])

    def experts(h, w):
        logits = mm(h, w["router"])
        top, idx = jax.lax.top_k(logits, sc["num_experts_per_tok"])
        gates = jax.nn.softmax(top, axis=-1)
        f = s["f"]
        out = jnp.zeros_like(h)
        for j, e in enumerate(sc["experts_held"]):
            gate = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
            gu = w["w_gate_up"][j]
            out = out + gate[..., None] * ffn(h, gu[:, :f], gu[:, f:], w["w_down"][j])
        return out

    def layer(mixer):
        def f(w, x):
            x = x + r * mixer(rmsnorm(x, w["norm"]), w)
            h = rmsnorm(x, w["ffn_norm"])
            return x + r * (experts(h, w) + ffn(h, w["shared_gate"], w["shared_up"],
                                                w["shared_down"]))
        return jax.jit(f)

    def head(emb, x, labels, mask):
        x = rmsnorm(x, final_gain(sc))
        logits = mm(x, emb.T) / sc["logits_scaling"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - picked) * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)

    return {"mamba": layer(mamba), "attention": layer(attention)}, jax.jit(head)


def row_losses(sc: Dict[str, Any], texts: Sequence[str], seq: int, weights=None,
               quantize: Optional[str] = None, rows_per_call: int = 16) -> np.ndarray:
    """Per-row mean loss of ``texts`` at the highest matmul precision, one
    layer at a time over every row (in calls of ``rows_per_call`` rows), so
    that only one layer's weights are on the device at once."""
    import jax
    import jax.numpy as jnp

    if not texts:
        return np.zeros(0)
    layers, head = make_layers(sc, quantize)
    toks, labels, mask = encode(texts, sc["vocab_size"], seq)
    n = len(texts)
    pad = -n % rows_per_call  # one compiled shape
    toks, labels, mask = (np.pad(a, ((0, pad), (0, 0))) for a in (toks, labels, mask))
    calls = range(0, n + pad, rows_per_call)
    with jax.default_matmul_precision("highest"):
        emb = weights["embed"] if weights else embedding(sc)
        qemb = _round(emb, None if quantize is None else jnp.dtype(quantize))
        xs = [qemb[toks[i:i + rows_per_call]] * sc["embedding_multiplier"] for i in calls]
        for i, kind in enumerate(sc["layer_types"]):
            w = weights["layers"][i] if weights else layer_weights(sc, i)
            xs = [layers[kind](w, x) for x in xs]
            del w
        out = [np.asarray(head(emb, x, labels[i:i + rows_per_call], mask[i:i + rows_per_call]),
                          np.float64) for x, i in zip(xs, calls)]
    return np.concatenate(out)[:n]
