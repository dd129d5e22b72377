"""RecurrentGemma / Griffin hybrid LM [arXiv:2402.19427].

Layer pattern per super-block: (rec, rec, attn) — two RG-LRU recurrent
blocks then one local-MQA-attention block, each followed by an MLP. 38
layers = 12 scanned super-blocks + a 2-layer recurrent tail. The RG-LRU is
a gated diagonal linear recurrence evaluated with
``jax.lax.associative_scan`` (log-depth, TPU-friendly); gates are diagonal
(per-channel) — documented simplification vs. the paper's block-diagonal
projections. Decode keeps O(1) recurrent state + a window-sized attention
ring, which makes ``long_500k`` feasible.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.sharding import logical_constraint
from repro.models import layers as L
from repro.models import module as mod
from repro.models.decode_attn import decode_attention
from repro.models.transformer import remat_wrap, CACHE_DTYPE

LRU_C = 8.0
STATE_DTYPE = jnp.float32


def rglru_scan(x: jax.Array, a: jax.Array, init: jax.Array | None = None):
    """h_t = a_t h_{t-1} + x_t via associative scan. x, a: (b, s, w)."""
    if init is not None:
        # fold the initial state into the first step
        x = x.at[:, 0].add(a[:, 0] * init)
    def op(l, r):
        al, xl = l
        ar, xr = r
        return al * ar, xl * ar + xr
    _, h = jax.lax.associative_scan(op, (a, x), axis=1)
    return h


def rglru(x: jax.Array, lam, gx_w, gx_b, ga_w, ga_b, init=None):
    """RG-LRU (diagonal gates). x: (b, s, w) -> (h, last_state)."""
    x32 = x.astype(jnp.float32)
    i_t = jax.nn.sigmoid(x32 * gx_w + gx_b)
    r_t = jax.nn.sigmoid(x32 * ga_w + ga_b)
    log_a = -LRU_C * jax.nn.softplus(lam.astype(jnp.float32)) * r_t
    a_t = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i_t * x32)
    h = rglru_scan(gated, a_t, init)
    return h.astype(x.dtype), h[:, -1]


def rglru_step(state, x, lam, gx_w, gx_b, ga_w, ga_b):
    """Single decode step. state, x: (b, w)."""
    x32 = x.astype(jnp.float32)
    i_t = jax.nn.sigmoid(x32 * gx_w + gx_b)
    r_t = jax.nn.sigmoid(x32 * ga_w + ga_b)
    log_a = -LRU_C * jax.nn.softplus(lam.astype(jnp.float32)) * r_t
    a_t = jnp.exp(log_a)
    h = a_t * state + jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i_t * x32)
    return h, h.astype(x.dtype)


class GriffinLM:
    def __init__(self, cfg: ModelConfig, remat_policy: str = "full"):
        self.cfg = cfg
        self.remat_policy = remat_policy
        pat = cfg.hybrid_pattern or ("rec", "rec", "attn")
        self.pattern = pat
        self.n_super = cfg.n_layers // len(pat)
        self.n_tail = cfg.n_layers - self.n_super * len(pat)
        # tail layers follow the pattern prefix (all 'rec' for 38 = 12*3 + 2)
        self.tail_kinds = pat[: self.n_tail]
        assert all(k == "rec" for k in self.tail_kinds), "tail must be recurrent"
        self.rec_per_super = sum(1 for k in pat if k == "rec")
        self.attn_per_super = sum(1 for k in pat if k == "attn")

    # ------------------------------------------------------------------
    def _rec_specs(self, n: int, prefix_axis="layers") -> Dict[str, mod.ParamSpec]:
        c = self.cfg
        d, w = c.d_model, (c.lru_width or c.d_model)
        sp = lambda shape, axes, **kw: mod.spec((n,) + shape, (prefix_axis,) + axes, **kw)
        return {
            "norm1": sp((d,), ("embed",), init="ones"),
            "w_x": sp((d, w), ("embed", "lru"), init="scaled"),
            "w_y": sp((d, w), ("embed", "lru"), init="scaled"),
            "conv_w": sp((w, 4), ("lru", "conv"), init="scaled"),
            "conv_b": sp((w,), ("lru",), init="zeros"),
            "lam": sp((w,), ("lru",), init="normal", scale=0.5),
            "gx_w": sp((w,), ("lru",), init="ones"),
            "gx_b": sp((w,), ("lru",), init="zeros"),
            "ga_w": sp((w,), ("lru",), init="ones"),
            "ga_b": sp((w,), ("lru",), init="zeros"),
            "w_out": sp((w, d), ("lru", "embed"), init="scaled"),
            "norm2": sp((d,), ("embed",), init="ones"),
            "wg": sp((d, c.d_ff), ("embed", "mlp"), init="scaled"),
            "wu": sp((d, c.d_ff), ("embed", "mlp"), init="scaled"),
            "wd": sp((c.d_ff, d), ("mlp", "embed"), init="scaled"),
        }

    def _attn_specs(self, n: int) -> Dict[str, mod.ParamSpec]:
        c = self.cfg
        d, hd = c.d_model, c.resolved_head_dim
        qd, kvd = c.n_heads * hd, c.n_kv_heads * hd
        sp = lambda shape, axes, **kw: mod.spec((n,) + shape, ("layers",) + axes, **kw)
        return {
            "norm1": sp((d,), ("embed",), init="ones"),
            "wq": sp((d, qd), ("embed", "heads"), init="scaled"),
            "wk": sp((d, kvd), ("embed", "kv_heads"), init="scaled"),
            "wv": sp((d, kvd), ("embed", "kv_heads"), init="scaled"),
            "wo": sp((qd, d), ("heads", "embed"), init="scaled"),
            "norm2": sp((d,), ("embed",), init="ones"),
            "wg": sp((d, c.d_ff), ("embed", "mlp"), init="scaled"),
            "wu": sp((d, c.d_ff), ("embed", "mlp"), init="scaled"),
            "wd": sp((c.d_ff, d), ("mlp", "embed"), init="scaled"),
        }

    def param_specs(self):
        c = self.cfg
        p: Dict[str, Any] = {
            "embed": mod.spec((c.padded_vocab, c.d_model), ("vocab", "embed")),
            "final_norm": mod.spec((c.d_model,), ("embed",), init="ones"),
            "head": mod.spec((c.d_model, c.padded_vocab), ("embed", "vocab"), init="scaled"),
            "super": {
                "rec0": self._rec_specs(self.n_super),
                "rec1": self._rec_specs(self.n_super),
                "attn": self._attn_specs(self.n_super),
            },
        }
        if self.n_tail:
            p["tail"] = self._rec_specs(self.n_tail)
        return p

    def init_params(self, key):
        return mod.init_tree(self.param_specs(), key)

    # ------------------------------------------------------------------
    def _mlp(self, p, x):
        h = L.rms_norm(x, p["norm2"], self.cfg.norm_eps)
        return x + L.mlp_swiglu(h, p["wg"], p["wu"], p["wd"])

    def _rec_block(self, p, x, mode, state=None):
        c = self.cfg
        h = L.rms_norm(x, p["norm1"], c.norm_eps)
        b1 = jnp.einsum("bsd,dw->bsw", h, p["w_x"].astype(h.dtype))
        b2 = jnp.einsum("bsd,dw->bsw", h, p["w_y"].astype(h.dtype))
        b2 = jax.nn.gelu(b2.astype(jnp.float32), approximate=True).astype(h.dtype)
        if mode == "decode":
            conv_state, lru_state = state  # (b, 3, w), (b, w)
            seq = jnp.concatenate([conv_state.astype(b1.dtype), b1], axis=1)
            from repro.models.mamba2 import causal_conv1d
            conv_out = causal_conv1d(seq, p["conv_w"], p["conv_b"])[:, -1]
            new_conv = seq[:, 1:].astype(conv_state.dtype)
            lru_state, y = rglru_step(
                lru_state, conv_out, p["lam"], p["gx_w"], p["gx_b"], p["ga_w"], p["ga_b"]
            )
            y = y[:, None]  # (b, 1, w)
            new_state = (new_conv, lru_state)
        else:
            from repro.models.mamba2 import causal_conv1d
            conv_out = causal_conv1d(b1, p["conv_w"], p["conv_b"])
            y, last = rglru(
                conv_out, p["lam"], p["gx_w"], p["gx_b"], p["ga_w"], p["ga_b"]
            )
            if mode == "train":
                new_state = None
            else:  # prefill: emit decode-ready state
                conv_tail = b1[:, -3:].astype(STATE_DTYPE)
                new_state = (conv_tail, last)
        merged = y * b2
        out = jnp.einsum("bsw,wd->bsd", merged, p["w_out"].astype(x.dtype))
        x = logical_constraint(x + out, ("batch", "seq", "embed"))
        return self._mlp(p, x), new_state

    def _attn_block(self, p, x, positions, mode, state=None, pos=None):
        c = self.cfg
        hd = c.resolved_head_dim
        h = L.rms_norm(x, p["norm1"], c.norm_eps)
        b, s, _ = h.shape
        q = jnp.einsum("bsd,dq->bsq", h, p["wq"].astype(h.dtype)).reshape(b, s, c.n_heads, hd)
        k = jnp.einsum("bsd,dq->bsq", h, p["wk"].astype(h.dtype)).reshape(b, s, c.n_kv_heads, hd)
        v = jnp.einsum("bsd,dq->bsq", h, p["wv"].astype(h.dtype)).reshape(b, s, c.n_kv_heads, hd)
        q = L.apply_rope(q, positions, c.rope_theta)
        k = L.apply_rope(k, positions, c.rope_theta)
        if mode == "decode":
            kst, vst, i = state  # stacked (n_super, b, hkv, A, hd)
            attn, kst, vst = decode_attention(q, k, v, kst, vst, i, pos)
            new_state = (kst, vst)
        else:
            attn = L.attention_chunked(q, k, v, causal=True, window=c.local_window)
            if mode == "train":
                new_state = None
            else:
                a = min(s, c.local_window)
                new_state = (
                    L.cache_store(k[:, -a:]).astype(CACHE_DTYPE),
                    L.cache_store(v[:, -a:]).astype(CACHE_DTYPE),
                )
        attn = jnp.einsum("bsq,qd->bsd", attn.reshape(b, s, -1), p["wo"].astype(x.dtype))
        x = logical_constraint(x + attn, ("batch", "seq", "embed"))
        return self._mlp(p, x), new_state

    # ------------------------------------------------------------------
    def _super_block(self, p, x, positions, mode, state=None, pos=None):
        """train/prefill super-block (decode is handled in _forward)."""
        st = state or {}
        x, s0 = self._rec_block(p["rec0"], x, mode, st.get("rec0"))
        x, s1 = self._rec_block(p["rec1"], x, mode, st.get("rec1"))
        x, sa = self._attn_block(p["attn"], x, positions, mode, st.get("attn"), pos)
        return x, {"rec0": s0, "rec1": s1, "attn": sa}

    def _forward(self, params, x, positions, mode, cache=None, pos=None):
        """Shared over train/prefill/decode. Returns (x, new_cache)."""
        if mode == "decode":
            kst, vst = cache["super"]["attn"]
            rec_st = {"rec0": cache["super"]["rec0"], "rec1": cache["super"]["rec1"]}

            def scan_dec(carry, per):
                xx, kc, vc = carry
                pp, rst, i = per
                xx, s0 = self._rec_block(pp["rec0"], xx, mode, rst["rec0"])
                xx, s1 = self._rec_block(pp["rec1"], xx, mode, rst["rec1"])
                xx, (kc, vc) = self._attn_block(
                    pp["attn"], xx, positions, mode, (kc, vc, i), pos
                )
                return (xx, kc, vc), {"rec0": s0, "rec1": s1}

            (x, kst, vst), new_rec = jax.lax.scan(
                scan_dec, (x, kst, vst),
                (params["super"], rec_st, jnp.arange(self.n_super)),
            )
            new_super = {**new_rec, "attn": (kst, vst)}
        else:
            blk = remat_wrap(
                lambda xx, pp: self._super_block(pp, xx, positions, mode, None, pos),
                self.remat_policy,
            )

            def scan_train(xx, pp):
                xx, new_st = blk(xx, pp)
                return xx, new_st

            x, new_super = jax.lax.scan(scan_train, x, params["super"])

        new_tail = None
        if self.n_tail:
            tails = []
            for i in range(self.n_tail):
                pp = jax.tree.map(lambda a: a[i], params["tail"])
                t_st = None
                if mode == "decode":
                    t_st = jax.tree.map(lambda a: a[i], cache["tail"])
                x, t_new = self._rec_block(pp, x, mode, t_st)
                tails.append(t_new)
            new_tail = jax.tree.map(lambda *xs: jnp.stack(xs), *tails)
        return x, {"super": new_super, "tail": new_tail}

    # ------------------------------------------------------------------
    def logits(self, params, tokens):
        """Full-sequence logits (b, s, padded vocab)."""
        c = self.cfg
        x = L.embed(tokens, params["embed"])
        x = logical_constraint(x, ("batch", "seq", "embed"))
        positions = jnp.arange(x.shape[1])
        x, _ = self._forward(params, x, positions, "train")
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        logits = L.lm_logits(x, params["head"])
        return logical_constraint(logits, ("batch", "seq", "vocab"))

    def loss_fn(self, params, batch):
        logits = self.logits(params, batch["tokens"])
        loss = L.softmax_xent(logits, batch["labels"], batch.get("loss_mask"),
                              valid_vocab=self.cfg.vocab_size)
        return loss, {"xent": loss}

    def row_losses(self, params, batch):
        """``((B,) mean loss of each row over its masked positions, {})``."""
        logits = self.logits(params, batch["tokens"])
        return L.row_xent(logits, batch["labels"], batch["loss_mask"],
                          valid_vocab=self.cfg.vocab_size), {}

    def prefill(self, params, batch, cache_budget: int = 0):
        # local-attention caches are windowed rings: no budget needed
        c = self.cfg
        x = L.embed(batch["tokens"], params["embed"])
        positions = jnp.arange(x.shape[1])
        x, cache = self._forward(params, x, positions, "prefill")
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        logits = L.lm_logits(x[:, -1:], params["head"])[..., : c.vocab_size]
        return cache, logits

    def decode_step(self, params, cache, batch):
        c = self.cfg
        x = L.embed(batch["token"], params["embed"])
        pos = batch["pos"]
        positions = jnp.asarray(pos)[None]
        x, cache = self._forward(params, x, positions, "decode", cache, pos)
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        logits = L.lm_logits(x, params["head"])[..., : c.vocab_size]
        return cache, logits

    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            return {
                "tokens": mod.spec((b, s), ("batch", "seq"), i32, "zeros"),
                "labels": mod.spec((b, s), ("batch", "seq"), i32, "zeros"),
                "loss_mask": mod.spec((b, s), ("batch", "seq"), jnp.float32, "ones"),
            }
        if shape.kind == "prefill":
            return {"tokens": mod.spec((b, s), ("batch", "seq"), i32, "zeros")}
        return {
            "token": mod.spec((b, 1), ("batch", "seq"), i32, "zeros"),
            "pos": mod.spec((), (), i32, "zeros"),
        }

    def cache_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        c = self.cfg
        b = shape.global_batch
        w = c.lru_width or c.d_model
        a = min(shape.seq_len, c.local_window)
        hd = c.resolved_head_dim
        n = self.n_super
        rec = lambda nn: (
            mod.spec((nn, b, 3, w), ("layers", "cache_batch", None, "lru"), STATE_DTYPE, "zeros"),
            mod.spec((nn, b, w), ("layers", "cache_batch", "lru"), STATE_DTYPE, "zeros"),
        )
        attn = (
            mod.spec((n, b, c.n_kv_heads, a, hd), ("layers", "cache_batch", "kv_heads", "kv_seq", None), CACHE_DTYPE, "zeros"),
            mod.spec((n, b, c.n_kv_heads, a, hd), ("layers", "cache_batch", "kv_heads", "kv_seq", None), CACHE_DTYPE, "zeros"),
        )
        out: Dict[str, Any] = {
            "super": {"rec0": rec(n), "rec1": rec(n), "attn": attn}
        }
        out["tail"] = rec(self.n_tail) if self.n_tail else None
        return out
