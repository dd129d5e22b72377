"""Decoder-only transformer LM covering the dense / moe / vlm families.

Uniform model API (shared by all families in ``repro.models``):
  * ``param_specs()``           -> ParamSpec tree (shapes + logical axes)
  * ``init_params(key)``        -> materialised params (reduced configs)
  * ``loss_fn(params, batch)``  -> (loss, metrics)         [train shapes]
  * ``prefill(params, batch)``  -> (cache, last_logits)    [prefill shapes]
  * ``decode_step(params, cache, batch)`` -> (cache, logits) [decode shapes]
  * ``input_specs(shape)`` / ``cache_specs(shape)`` -> ShapeDtypeStruct trees

Layers are stacked on a leading ``layers`` dim and executed with
``jax.lax.scan`` (+ selectable remat policy) so giant configs compile fast
and the dry-run HLO stays compact.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.sharding import logical_constraint
from repro.models import layers as L
from repro.models import module as mod
from repro.models.decode_attn import decode_attention
from repro.models.moe import moe_layer

CACHE_DTYPE = jnp.bfloat16
MOE_AUX_COEF = 0.01


def remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)  # 'full': save nothing


class TransformerLM:
    def __init__(self, cfg: ModelConfig, remat_policy: str = "full",
                 moe_dispatch: str = "scatter"):
        self.cfg = cfg
        self.remat_policy = remat_policy
        self.moe_dispatch = moe_dispatch

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------
    def _layer_specs(self) -> Dict[str, mod.ParamSpec]:
        c = self.cfg
        nl, d, f = c.n_layers, c.d_model, c.d_ff
        qd, kvd = c.q_dim, c.kv_dim
        s: Dict[str, mod.ParamSpec] = {
            "norm1": mod.spec((nl, d), ("layers", "embed"), init="ones"),
            "wq": mod.spec((nl, d, qd), ("layers", "embed", "heads"), init="scaled"),
            "wk": mod.spec((nl, d, kvd), ("layers", "embed", "kv_heads"), init="scaled"),
            "wv": mod.spec((nl, d, kvd), ("layers", "embed", "kv_heads"), init="scaled"),
            "wo": mod.spec((nl, qd, d), ("layers", "heads", "embed"), init="scaled"),
            "norm2": mod.spec((nl, d), ("layers", "embed"), init="ones"),
        }
        if c.qkv_bias:
            s["bq"] = mod.spec((nl, qd), ("layers", "heads"), init="zeros")
            s["bk"] = mod.spec((nl, kvd), ("layers", "kv_heads"), init="zeros")
            s["bv"] = mod.spec((nl, kvd), ("layers", "kv_heads"), init="zeros")
        if c.family == "moe":
            e = c.n_experts
            s["router"] = mod.spec((nl, d, e), ("layers", "embed", "expert"), init="scaled")
            s["eg"] = mod.spec((nl, e, d, f), ("layers", "expert", "embed", "mlp"), init="scaled")
            s["eu"] = mod.spec((nl, e, d, f), ("layers", "expert", "embed", "mlp"), init="scaled")
            s["ed"] = mod.spec((nl, e, f, d), ("layers", "expert", "mlp", "embed"), init="scaled")
        elif c.mlp_type == "swiglu":
            s["wg"] = mod.spec((nl, d, f), ("layers", "embed", "mlp"), init="scaled")
            s["wu"] = mod.spec((nl, d, f), ("layers", "embed", "mlp"), init="scaled")
            s["wd"] = mod.spec((nl, f, d), ("layers", "mlp", "embed"), init="scaled")
        else:  # gelu
            s["wu"] = mod.spec((nl, d, f), ("layers", "embed", "mlp"), init="scaled")
            s["wd"] = mod.spec((nl, f, d), ("layers", "mlp", "embed"), init="scaled")
            s["bu"] = mod.spec((nl, f), ("layers", "mlp"), init="zeros")
            s["bd"] = mod.spec((nl, d), ("layers", "embed"), init="zeros")
        return s

    def param_specs(self):
        c = self.cfg
        p: Dict[str, Any] = {
            "embed": mod.spec((c.padded_vocab, c.d_model), ("vocab", "embed")),
            "layers": self._layer_specs(),
            "final_norm": mod.spec((c.d_model,), ("embed",), init="ones"),
        }
        if not c.tie_embeddings:
            p["head"] = mod.spec((c.d_model, c.padded_vocab), ("embed", "vocab"), init="scaled")
        if c.family == "vlm":
            p["patch_proj"] = mod.spec(
                (c.d_model, c.d_model), ("embed", "embed"), init="scaled"
            )
        return p

    def init_params(self, key):
        return mod.init_tree(self.param_specs(), key)

    # ------------------------------------------------------------------
    # One transformer block
    # ------------------------------------------------------------------
    def _qkv(self, p, h, positions):
        c = self.cfg
        hd = c.resolved_head_dim
        b, s, _ = h.shape
        q = jnp.einsum("bsd,dq->bsq", h, p["wq"].astype(h.dtype))
        k = jnp.einsum("bsd,dq->bsq", h, p["wk"].astype(h.dtype))
        v = jnp.einsum("bsd,dq->bsq", h, p["wv"].astype(h.dtype))
        if c.qkv_bias:
            q = q + p["bq"].astype(h.dtype)
            k = k + p["bk"].astype(h.dtype)
            v = v + p["bv"].astype(h.dtype)
        q = q.reshape(b, s, c.n_heads, hd)
        k = k.reshape(b, s, c.n_kv_heads, hd)
        v = v.reshape(b, s, c.n_kv_heads, hd)
        q = L.apply_rope(q, positions, c.rope_theta)
        k = L.apply_rope(k, positions, c.rope_theta)
        return q, k, v

    def _mlp(self, p, h):
        c = self.cfg
        if c.family == "moe":
            out, aux = moe_layer(
                h, p["router"], p["eg"], p["eu"], p["ed"], c.top_k,
                c.capacity_factor, dispatch=self.moe_dispatch,
            )
            return out, aux
        if c.mlp_type == "swiglu":
            return L.mlp_swiglu(h, p["wg"], p["wu"], p["wd"]), 0.0
        return L.mlp_gelu(h, p["wu"], p["wd"], p.get("bu"), p.get("bd")), 0.0

    def _block_train(self, p, x, positions):
        c = self.cfg
        h = L.rms_norm(x, p["norm1"], c.norm_eps)
        q, k, v = self._qkv(p, h, positions)
        attn = L.attention_chunked(q, k, v, causal=True, window=c.attn_window)
        attn = jnp.einsum(
            "bsq,qd->bsd",
            attn.reshape(attn.shape[0], attn.shape[1], -1),
            p["wo"].astype(x.dtype),
        )
        x = x + attn
        x = logical_constraint(x, ("batch", "seq", "embed"))
        h = L.rms_norm(x, p["norm2"], c.norm_eps)
        m, aux = self._mlp(p, h)
        x = x + m
        x = logical_constraint(x, ("batch", "seq", "embed"))
        return x, aux

    # ------------------------------------------------------------------
    # Train
    # ------------------------------------------------------------------
    def _backbone_inputs(self, params, batch):
        """Token (+patch) embedding. Returns x (b, s, d)."""
        c = self.cfg
        x = L.embed(batch["tokens"], params["embed"])
        if c.family == "vlm":
            pe = batch["patch_embeds"].astype(x.dtype)
            pe = jnp.einsum("bpd,de->bpe", pe, params["patch_proj"].astype(x.dtype))
            x = jnp.concatenate([pe, x], axis=1)
        return logical_constraint(x, ("batch", "seq", "embed"))

    def logits(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence logits (b, s, padded vocab) and the layers' MoE
        auxiliary losses."""
        c = self.cfg
        x = self._backbone_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        block = remat_wrap(
            lambda xx, pp: self._block_train(pp, xx, positions), self.remat_policy
        )

        def scan_body(xx, pp):
            xx, aux = block(xx, pp)
            return xx, aux

        x, auxs = jax.lax.scan(scan_body, x, params["layers"])
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        if c.family == "vlm":  # drop patch positions before the LM head
            x = x[:, c.n_patches :]
        logits = L.lm_logits(x, head)
        return logical_constraint(logits, ("batch", "seq", "vocab")), auxs

    def loss_fn(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        c = self.cfg
        logits, auxs = self.logits(params, batch)
        loss = L.softmax_xent(logits, batch["labels"], batch.get("loss_mask"), valid_vocab=c.vocab_size)
        aux = jnp.sum(auxs) if c.family == "moe" else 0.0
        total = loss + MOE_AUX_COEF * aux
        return total, {"xent": loss, "moe_aux": jnp.asarray(aux, jnp.float32)}

    def row_losses(self, params, batch):
        """``((B,) mean loss of each row over its masked positions, {})``."""
        logits, _ = self.logits(params, batch)
        return L.row_xent(logits, batch["labels"], batch["loss_mask"],
                          valid_vocab=self.cfg.vocab_size), {}

    # ------------------------------------------------------------------
    # Serve: prefill + decode
    # ------------------------------------------------------------------
    def cache_len(self, seq_len: int) -> int:
        c = self.cfg
        return min(seq_len, c.attn_window) if c.attn_window else seq_len

    def _block_prefill(self, p, x, positions, a_alloc: int):
        """Like _block_train but also emits this layer's (k, v) cache."""
        c = self.cfg
        h = L.rms_norm(x, p["norm1"], c.norm_eps)
        q, k, v = self._qkv(p, h, positions)
        attn = L.attention_chunked(q, k, v, causal=True, window=c.attn_window)
        attn = jnp.einsum(
            "bsq,qd->bsd", attn.reshape(attn.shape[0], attn.shape[1], -1),
            p["wo"].astype(x.dtype),
        )
        x = x + attn
        h = L.rms_norm(x, p["norm2"], c.norm_eps)
        m, _ = self._mlp(p, h)
        x = x + m
        x = logical_constraint(x, ("batch", "seq", "embed"))
        s = x.shape[1]
        if a_alloc <= s:
            # ring layout: position p -> slot p % a; holds when s % a == 0
            # (asserted in input_specs for the assigned shapes)
            k_c, v_c = k[:, -a_alloc:], v[:, -a_alloc:]
        else:  # full-attention cache with decode budget appended
            pad = ((0, 0), (0, a_alloc - s), (0, 0), (0, 0))
            k_c, v_c = jnp.pad(k, pad), jnp.pad(v, pad)
        cache_axes = ("batch", "kv_heads", "kv_seq", None)
        k_c = logical_constraint(L.cache_store(k_c).astype(CACHE_DTYPE), cache_axes)
        v_c = logical_constraint(L.cache_store(v_c).astype(CACHE_DTYPE), cache_axes)
        return x, (k_c, v_c)

    def prefill(self, params, batch, cache_budget: int = 0):
        c = self.cfg
        x = self._backbone_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        s = x.shape[1]
        a_alloc = self.cache_len(s) if c.attn_window else s + cache_budget
        block = remat_wrap(
            lambda xx, pp: self._block_prefill(pp, xx, positions, a_alloc),
            self.remat_policy,
        )
        x, (k_all, v_all) = jax.lax.scan(lambda xx, pp: block(xx, pp), x, params["layers"])
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        last = x[:, -1:]
        logits = L.lm_logits(last, head)[..., : c.vocab_size]
        cache = {"k": k_all, "v": v_all}  # (L, b, A, hkv, hd)
        return cache, logits

    def _block_decode(self, p, x, kst, vst, i, pos, slot):
        """x: (b, 1, d); kst/vst: full stacked cache (L, b, hkv, A, hd).

        The new token's K/V is written as a single-slot slice into the
        stacked cache (carried through the layer scan), so with donation the
        update is in-place — per-layer traffic is one cache READ plus a
        token-sized write, never a full-slice rewrite.
        """
        c = self.cfg
        h = L.rms_norm(x, p["norm1"], c.norm_eps)
        q, k, v = self._qkv(p, h, jnp.array([pos]) if not isinstance(pos, jax.Array) else pos[None])
        attn, kst, vst = decode_attention(q, k, v, kst, vst, i, pos)
        attn = jnp.einsum(
            "bsq,qd->bsd", attn.reshape(attn.shape[0], 1, -1), p["wo"].astype(x.dtype)
        )
        x = x + attn
        h = L.rms_norm(x, p["norm2"], c.norm_eps)
        m, _ = self._mlp(p, h)
        x = x + m
        return x, kst, vst

    def decode_step(self, params, cache, batch):
        """batch: {'token': (b, 1) int32, 'pos': scalar int32}."""
        c = self.cfg
        x = L.embed(batch["token"], params["embed"])
        x = logical_constraint(x, ("batch", "seq", "embed"))
        pos = jnp.asarray(batch["pos"])
        kst, vst = cache["k"], cache["v"]
        slot = pos % kst.shape[3]

        def scan_body(carry, per_layer):
            xx, kc, vc = carry
            pp, i = per_layer
            xx, kc, vc = self._block_decode(pp, xx, kc, vc, i, pos, slot)
            return (xx, kc, vc), None

        (x, kst, vst), _ = jax.lax.scan(
            scan_body, (x, kst, vst), (params["layers"], jnp.arange(c.n_layers))
        )
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        logits = L.lm_logits(x, head)[..., : c.vocab_size]
        return {"k": kst, "v": vst}, logits

    # ------------------------------------------------------------------
    # Dry-run specs
    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        c = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            text = s - (c.n_patches if c.family == "vlm" else 0)
            d: Dict[str, Any] = {
                "tokens": mod.spec((b, text), ("batch", "seq"), i32, "zeros"),
                "labels": mod.spec((b, s if c.family != "vlm" else text), ("batch", "seq"), i32, "zeros"),
                "loss_mask": mod.spec((b, s if c.family != "vlm" else text), ("batch", "seq"), jnp.float32, "ones"),
            }
            if c.family == "vlm":
                d["patch_embeds"] = mod.spec(
                    (b, c.n_patches, c.d_model), ("batch", "seq", "embed"), jnp.bfloat16
                )
            return d
        if shape.kind == "prefill":
            text = s - (c.n_patches if c.family == "vlm" else 0)
            d = {"tokens": mod.spec((b, text), ("batch", "seq"), i32, "zeros")}
            if c.family == "vlm":
                d["patch_embeds"] = mod.spec(
                    (b, c.n_patches, c.d_model), ("batch", "seq", "embed"), jnp.bfloat16
                )
            return d
        # decode: one new token against a cache of seq_len
        if c.attn_window:
            assert s % c.attn_window == 0 or s < c.attn_window, (
                "ring-buffer prefill assumes seq %% window == 0"
            )
        return {
            "token": mod.spec((b, 1), ("batch", "seq"), i32, "zeros"),
            "pos": mod.spec((), (), i32, "zeros"),
        }

    def cache_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        c = self.cfg
        b = shape.global_batch
        a = self.cache_len(shape.seq_len)
        hd = c.resolved_head_dim
        kv = (c.n_layers, b, c.n_kv_heads, a, hd)
        axes = ("layers", "cache_batch", "kv_heads", "kv_seq", None)
        return {
            "k": mod.spec(kv, axes, CACHE_DTYPE, "zeros"),
            "v": mod.spec(kv, axes, CACHE_DTYPE, "zeros"),
        }
