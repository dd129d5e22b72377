"""Plain reference of the scorer: a Mamba-2 language model's per-row mean
next-token loss, in float32 ``jax.numpy`` at the highest matmul precision.

Written from the architecture (arXiv:2405.21060) as the configuration file
states it, with none of the program's code:

- tokens: the hashed-word tokenizer (``tokenize``);
- embedding ``E`` of ``padded_vocab`` rows, tied to the LM head; the loss is
  over the first ``vocab_size`` columns;
- each of ``n_layers`` blocks, pre-norm and residual:
  ``h = rmsnorm(x) * norm``; ``[z, xBC, dt] = h @ w_in``;
  ``xBC = silu(causal depthwise conv(xBC) + conv_b)`` (kernel ``ssm_conv``);
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(a_log)``; per head, the state
  ``S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T`` and
  ``y_t = S_t C_t + d_skip * x_t``, one position after another (no chunked
  scan); ``y = rmsnorm(y * silu(z)) * norm_g``; ``x = x + y @ w_out``;
- a final ``rmsnorm``, logits ``x @ E^T``.

Weights are made here from the rule the configuration states: ``PRNGKey(0)``
split once per leaf, leaves in sorted path order; ``N(0, 0.02^2)`` for the
embedding, ``N(0, 1/fan_in)`` for ``w_in``, ``w_out`` and ``conv_w``,
ones for the norms and ``d_skip``, zeros for the biases and ``a_log``.

``quantize`` makes the control: every matmul operand, the embedding table
included, rounded to the given lower-precision type (``float8_e4m3fn``)
under one scale per tensor, before a float32 product.
"""
from __future__ import annotations

import hashlib
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.costs import mamba2_flops_per_token

PAD, BOS, EOS = 0, 1, 2
# the scorer keys of the program's reduced mamba2-1.3b, short rows, few checked
TINY = {"n_layers": 2, "d_model": 64, "vocab_size": 512, "ssm_state": 16,
        "ssm_headdim": 16, "ssm_chunk": 8, "seq_len": 16, "rows_checked": 16}


def tokenize(text: str, vocab_size: int) -> List[int]:
    """``[BOS] + one id per whitespace word + [EOS]``; a word's id is
    ``4 + blake2b-64(word) % (vocab_size - 4)``."""
    ids = [4 + int.from_bytes(hashlib.blake2b(w.encode("utf-8"), digest_size=8).digest(),
                              "little") % (vocab_size - 4) for w in text.split()]
    return [BOS] + ids + [EOS]


def encode(texts: Sequence[str], vocab_size: int, seq: int
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Next-token rows: tokens, labels and a mask over the scored positions,
    each ``(len(texts), seq)``. A row scores ``n = min(len(ids) - 1, seq)``
    positions; the target of position ``j`` is ``ids[j + 1]``: the next
    word, ``EOS`` after the last word, or the first word past the cut."""
    toks = np.zeros((len(texts), seq), np.int32)
    labels = np.zeros((len(texts), seq), np.int32)
    mask = np.zeros((len(texts), seq), np.float32)
    for i, t in enumerate(texts):
        ids = tokenize(t, vocab_size)[:seq + 1]
        n = len(ids) - 1
        toks[i, :n] = ids[:-1]
        labels[i, :n] = ids[1:]
        mask[i, :n] = 1.0
    return toks, labels, mask


def real_tokens(text: str, seq: int) -> int:
    """Positions the scorer scores for one text: words + 1, at most ``seq``."""
    return min(len(text.split()) + 1, seq)


def flops_per_token(cfg: Dict[str, Any]) -> int:
    """Forward FLOPs per token of the chunked SSD at the configured chunk."""
    return mamba2_flops_per_token(cfg, cfg["ssm_chunk"])


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    nh = di // cfg["ssm_headdim"]
    gds = cfg["ssm_ngroups"] * cfg["ssm_state"]
    m = cfg["vocab_pad_multiple"]
    return {"d": d, "di": di, "nh": nh, "hd": cfg["ssm_headdim"], "ds": cfg["ssm_state"],
            "g": cfg["ssm_ngroups"], "conv_dim": di + 2 * gds, "proj": 2 * di + 2 * gds + nh,
            "vocab": cfg["vocab_size"], "padded_vocab": -(-cfg["vocab_size"] // m) * m,
            "k": cfg["ssm_conv"], "L": cfg["n_layers"]}


def init_weights(cfg: Dict[str, Any]):
    """The configuration's weights, float32, on the default device."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    L, d, di, nh, cd, pv = s["L"], s["d"], s["di"], s["nh"], s["conv_dim"], s["padded_vocab"]
    # (path, shape, rule): sorted path order is the order keys are split in
    leaves = [
        ("embed", (pv, d), "normal"),
        ("final_norm", (d,), "ones"),
        ("layers/a_log", (L, nh), "zeros"),
        ("layers/conv_b", (L, cd), "zeros"),
        ("layers/conv_w", (L, cd, s["k"]), "scaled"),
        ("layers/d_skip", (L, nh), "ones"),
        ("layers/dt_bias", (L, nh), "zeros"),
        ("layers/norm", (L, d), "ones"),
        ("layers/norm_g", (L, di), "ones"),
        ("layers/w_in", (L, d, s["proj"]), "scaled"),
        ("layers/w_out", (L, di, d), "scaled"),
    ]
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves))
    out = {}
    for (path, shape, rule), key in zip(leaves, keys):
        if rule == "zeros":
            v = jnp.zeros(shape, jnp.float32)
        elif rule == "ones":
            v = jnp.ones(shape, jnp.float32)
        else:
            std = 0.02 if rule == "normal" else 1.0 / math.sqrt(shape[-2])
            v = std * jax.random.normal(key, shape, jnp.float32)
        out[path.split("/")[-1]] = v
    return out


def _round(x, dtype):
    """``x`` rounded to ``dtype`` under one scale per tensor that maps its
    largest magnitude onto the type's largest finite value."""
    import jax.numpy as jnp

    if dtype is None:
        return x
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def make_loss_fn(cfg: Dict[str, Any], quantize: Optional[str] = None):
    """Jitted ``(weights, tokens, labels, mask) -> (B,)`` per-row mean loss."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    eps = cfg["norm_eps"]
    qt = None if quantize is None else jnp.dtype(quantize)

    def mm(a, b):
        return jnp.matmul(_round(a, qt), _round(b, qt))

    def rmsnorm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g

    def layer(x, w):
        bsz, T, _ = x.shape
        di, nh, hd, ds, g = s["di"], s["nh"], s["hd"], s["ds"], s["g"]
        zxbcdt = mm(rmsnorm(x, w["norm"]), w["w_in"])
        z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + s["conv_dim"]], zxbcdt[..., di + s["conv_dim"]:]
        k = s["k"]
        xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(xp[:, i:i + T] * w["conv_w"][:, i] for i in range(k)) + w["conv_b"]
        xbc = jax.nn.silu(conv)
        xs = xbc[..., :di].reshape(bsz, T, nh, hd)
        bm = xbc[..., di:di + g * ds].reshape(bsz, T, g, ds)
        cm = xbc[..., di + g * ds:].reshape(bsz, T, g, ds)
        rep = nh // g
        bm = jnp.repeat(bm, rep, axis=2)
        cm = jnp.repeat(cm, rep, axis=2)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        a = -jnp.exp(w["a_log"])

        def step(state, inp):
            x_t, b_t, c_t, dt_t = inp  # (B,nh,hd) (B,nh,ds) (B,nh,ds) (B,nh)
            state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                     + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
            return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

        state0 = jnp.zeros((bsz, nh, hd, ds), jnp.float32)
        seq = tuple(v.swapaxes(0, 1) for v in (xs, bm, cm, dt))
        _, ys = jax.lax.scan(step, state0, seq)
        y = ys.swapaxes(0, 1) + w["d_skip"][:, None] * xs
        y = y.reshape(bsz, T, di) * jax.nn.silu(z)
        return x + mm(rmsnorm(y, w["norm_g"]), w["w_out"])

    def loss(wts, tokens, labels, mask):
        emb = _round(wts["embed"], qt)
        x = emb[tokens]
        layers = {k: wts[k] for k in ("a_log", "conv_b", "conv_w", "d_skip", "dt_bias",
                                      "norm", "norm_g", "w_in", "w_out")}
        x, _ = jax.lax.scan(lambda h, w: (layer(h, w), None), x, layers)
        x = rmsnorm(x, wts["final_norm"])
        logits = mm(x, emb.T)[..., :s["vocab"]]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - picked) * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)

    return jax.jit(loss)


def row_losses(cfg: Dict[str, Any], texts: Sequence[str], seq: int, weights=None,
               quantize: Optional[str] = None, rows_per_call: int = 16) -> np.ndarray:
    """Per-row mean loss of ``texts`` at the highest matmul precision."""
    import jax

    if weights is None:
        weights = init_weights(cfg)
    fn = make_loss_fn(cfg, quantize)
    toks, labels, mask = encode(texts, cfg["vocab_size"], seq)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(texts), rows_per_call):
            sl = slice(i, i + rows_per_call)
            part = toks[sl], labels[sl], mask[sl]
            n = len(part[0])
            if n < rows_per_call:  # one compiled shape
                part = tuple(np.pad(p, ((0, rows_per_call - n), (0, 0))) for p in part)
            out.append(np.asarray(fn(weights, *part), np.float64)[:n])
    return np.concatenate(out) if out else np.zeros(0)
