"""Granite-4.0-H hybrid LM [hf:ibm-granite/granite-4.0-h-small]: each layer
is a Mamba-2 or an attention mixer, then a dropless mixture of experts plus
a shared expert; the configuration's ``layer_pattern`` gives the kind of
each layer and ``experts_held`` the experts this chip holds.

- ``x = embed(tokens) * embedding_multiplier``;
- each layer: ``x = x + r * mixer(rmsnorm(x))``, then ``h = rmsnorm(x)``
  and ``x = x + r * (moe_share(h) + shared(h))``, ``r`` the residual
  multiplier;
- the residual stream ``x`` is carried in float32, the mixers, experts and
  head run on its norms in the compute dtype: a bfloat16 stream biases
  every row's loss (0.030 nats on two layers at the published widths,
  0.0016 in float32; CPU);
- a final ``rmsnorm``, then ``logits = x @ E^T / logits_scaling``.

The Mamba-2 mixer is ``repro.models.mamba2.mixer``. The attention mixer is
causal GQA with scores scaled by ``attention_multiplier`` and, with
``use_rope`` off, no positional encoding. ``moe_share`` routes over all
experts and adds the held experts' part (``repro.models.moe``); ``shared``
and each expert are ``w_down(silu(h w_gate) * h w_up)``.

Weights (``init_params``): the embedding ``N(0, 0.02^2)`` from
``fold_in(key, 0)``; layer ``i``'s leaves, in sorted name order, from
``split(fold_in(key, i + 1), n_leaves)``; expert ``e`` of an expert leaf
from ``fold_in(leaf_key, e)``, so a chip's share is the whole layer's
weights at the held ids. Layer norms and ``d_skip`` are ones, biases and
``a_log`` zeros, matrices ``N(0, 1/fan_in)``. The final norm's weight is
``final_norm_gain``: with ones, a unit-RMS state against the tied
embedding would give logits of spread ``0.02 * sqrt(d) / logits_scaling``
(0.08 at the published widths), and every row's loss would sit at
``log(vocab)`` whatever the layers computed.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models import mamba2
from repro.models import module as mod
from repro.models.moe import moe_share
from repro.models.transformer import remat_wrap

CACHE_DTYPE = jnp.bfloat16
KINDS = ("mamba", "attention")
EMBED_STD = 0.02


def final_norm_gain(cfg: ModelConfig) -> float:
    """The final norm's weight: the gain at which the head's logits of a
    unit-RMS state have unit spread, ``logits_scaling / (EMBED_STD *
    sqrt(d_model))`` (12.5 at the published widths)."""
    return cfg.logits_scaling / (EMBED_STD * math.sqrt(cfg.d_model))


class GraniteHybridLM:
    def __init__(self, cfg: ModelConfig, remat_policy: str = "full"):
        assert len(cfg.layer_pattern) == cfg.n_layers, (cfg.layer_pattern, cfg.n_layers)
        self.cfg = cfg
        self.remat_policy = remat_policy
        self.held = cfg.held_experts
        # each kind's layers, in order; runs of consecutive layers of a kind
        self.layers_of = {k: [i for i, p in enumerate(cfg.layer_pattern) if p == k] for k in KINDS}
        self.runs = []
        for kind, grp in itertools.groupby(enumerate(cfg.layer_pattern), key=lambda ip: ip[1]):
            ids = [i for i, _ in grp]
            self.runs.append((kind, self.layers_of[kind].index(ids[0]), ids))
        self.attn_scale = cfg.attention_multiplier or None

    # ------------------------------------------------------------------
    def _attention_specs(self, nl: int) -> Dict[str, mod.ParamSpec]:
        c = self.cfg
        d, qd, kvd = c.d_model, c.q_dim, c.kv_dim
        return {
            "norm": mod.spec((nl, d), ("layers", "embed"), init="ones"),
            "wq": mod.spec((nl, d, qd), ("layers", "embed", "heads"), init="scaled"),
            "wk": mod.spec((nl, d, kvd), ("layers", "embed", "kv_heads"), init="scaled"),
            "wv": mod.spec((nl, d, kvd), ("layers", "embed", "kv_heads"), init="scaled"),
            "wo": mod.spec((nl, qd, d), ("layers", "heads", "embed"), init="scaled"),
        }

    def _ffn_specs(self, nl: int) -> Dict[str, mod.ParamSpec]:
        c = self.cfg
        d, f, fs, h = c.d_model, c.d_ff, c.shared_d_ff, len(self.held)
        return {
            "ffn_norm": mod.spec((nl, d), ("layers", "embed"), init="ones"),
            "router": mod.spec((nl, d, c.n_experts), ("layers", "embed", None), init="scaled"),
            "w_gate_up": mod.spec((nl, h, d, 2 * f), ("layers", "expert", "embed", "mlp"), init="expert"),
            "w_down": mod.spec((nl, h, f, d), ("layers", "expert", "mlp", "embed"), init="expert"),
            "shared_gate": mod.spec((nl, d, fs), ("layers", "embed", "mlp"), init="scaled"),
            "shared_up": mod.spec((nl, d, fs), ("layers", "embed", "mlp"), init="scaled"),
            "shared_down": mod.spec((nl, fs, d), ("layers", "mlp", "embed"), init="scaled"),
        }

    def param_specs(self):
        c = self.cfg
        p: Dict[str, Any] = {
            "embed": mod.spec((c.padded_vocab, c.d_model), ("vocab", "embed"), scale=EMBED_STD),
            "final_norm": mod.spec((c.d_model,), ("embed",), init="ones"),
        }
        for kind, ids in self.layers_of.items():
            if ids:
                mixer = (mamba2.mixer_specs(c, len(ids)) if kind == "mamba"
                         else self._attention_specs(len(ids)))
                p[kind] = {**mixer, **self._ffn_specs(len(ids))}
        dt = jnp.dtype(c.param_dtype)
        return mod.tree_map_specs(lambda s: dataclasses.replace(s, dtype=dt), p)

    def init_params(self, key):
        """The weights of the rule in the module docstring, made on the
        device in the configured dtype."""
        return jax.jit(self._init)(key)

    def _init(self, key):
        specs = self.param_specs()
        out = {"embed": mod._init_one(specs["embed"], jax.random.fold_in(key, 0)),
               "final_norm": jnp.full(specs["final_norm"].shape, final_norm_gain(self.cfg),
                                      specs["final_norm"].dtype)}
        for kind, ids in self.layers_of.items():
            if not ids:
                continue
            names = sorted(specs[kind])
            keys = jnp.stack([jax.random.split(jax.random.fold_in(key, i + 1), len(names))
                              for i in ids])  # (layers, leaves, 2)
            out[kind] = {n: jax.vmap(lambda k, s=specs[kind][n]: self._draw(s, k))(keys[:, j])
                         for j, n in enumerate(names)}
        return out

    def _draw(self, s: mod.ParamSpec, key):
        """One layer's slice of the stacked leaf ``s`` from its key."""
        one = dataclasses.replace(s, shape=s.shape[1:], axes=s.axes[1:])
        if s.init != "expert":
            return mod._init_one(one, key)
        each = dataclasses.replace(one, shape=one.shape[1:], axes=one.axes[1:], init="scaled")
        return jnp.stack([mod._init_one(each, jax.random.fold_in(key, e)) for e in self.held])

    # ------------------------------------------------------------------
    def _qkv(self, p, h, positions):
        c = self.cfg
        b, s, _ = h.shape
        hd = c.resolved_head_dim
        q = jnp.einsum("bsd,dq->bsq", h, p["wq"].astype(h.dtype)).reshape(b, s, c.n_heads, hd)
        k = jnp.einsum("bsd,dq->bsq", h, p["wk"].astype(h.dtype)).reshape(b, s, c.n_kv_heads, hd)
        v = jnp.einsum("bsd,dq->bsq", h, p["wv"].astype(h.dtype)).reshape(b, s, c.n_kv_heads, hd)
        if c.use_rope:
            q = L.apply_rope(q, positions, c.rope_theta)
            k = L.apply_rope(k, positions, c.rope_theta)
        return q, k, v

    def _out(self, p, o):
        return jnp.einsum("bsq,qd->bsd", o.reshape(*o.shape[:2], -1), p["wo"].astype(o.dtype))

    def _embed(self, params, tokens):
        """The float32 residual stream's start, ``embed(tokens) * m``."""
        return L.embed(tokens, params["embed"]).astype(jnp.float32) * self.cfg.embedding_multiplier

    def _norm(self, x, g):
        return L.rms_norm(x, g, self.cfg.norm_eps).astype(L.COMPUTE_DTYPE)

    def _add(self, x, out):
        return x + self.cfg.residual_multiplier * out.astype(x.dtype)

    def _ffn(self, p, x):
        """``(x + r * (moe_share(h) + shared(h)), slots)``, ``h = rmsnorm(x)``."""
        c = self.cfg
        h = self._norm(x, p["ffn_norm"])
        moe, slots = moe_share(h, p["router"], p["w_gate_up"], p["w_down"], self.held, c.top_k)
        shared = L.mlp_swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
        return self._add(x, moe + shared), slots

    def _layer(self, kind, p, x, mask):
        """One whole-sequence layer; also the held experts' assignment counts
        at the positions ``mask`` scores, (H,)."""
        c = self.cfg
        h = self._norm(x, p["norm"])
        if kind == "mamba":
            out = mamba2.mixer(c, p, h)[0]
        else:
            q, k, v = self._qkv(p, h, jnp.arange(h.shape[1]))
            out = self._out(p, L.attention_chunked(q, k, v, causal=True, scale=self.attn_scale))
        x, slots = self._ffn(p, self._add(x, out))
        hits = slots[..., None] == jnp.arange(len(self.held))
        return x, jnp.sum(hits * mask[:, :, None, None], axis=(0, 1, 2))

    def _trunk(self, params, tokens, mask):
        """Final hidden states (b, s, d) and the per-layer assignment counts
        of the held experts (n_layers, H)."""
        c = self.cfg
        x = self._embed(params, tokens)
        rows = jnp.zeros((c.n_layers, len(self.held)), jnp.float32)
        for kind, lo, ids in self.runs:
            layer = remat_wrap(lambda pp, xx, kind=kind: self._layer(kind, pp, xx, mask),
                               self.remat_policy)
            ids_a = jnp.asarray(ids)

            def body(j, carry, kind=kind, lo=lo, ids_a=ids_a, layer=layer):
                xx, rr = carry
                xx, r = layer(jax.tree.map(lambda a: a[j], params[kind]), xx)
                return xx, rr.at[ids_a[j - lo]].set(r)

            x, rows = jax.lax.fori_loop(lo, lo + len(ids), body, (x, rows))
        return x, rows

    def _head(self, params, x):
        c = self.cfg
        x = self._norm(x, params["final_norm"])
        # float32 out: a position's own token reads a logit of about 20
        # (its embedding is part of the state), where bfloat16 steps by 0.125
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return logits / c.logits_scaling

    def loss_fn(self, params, batch):
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(batch["tokens"].shape, jnp.float32)
        x, _ = self._trunk(params, batch["tokens"], mask)
        loss = L.softmax_xent(self._head(params, x), batch["labels"], mask,
                              valid_vocab=self.cfg.vocab_size)
        return loss, {"xent": loss}

    def row_losses(self, params, batch):
        """``((B,) mean loss of each row over its masked positions,
        {"moe_rows": (n_layers, H)})``: the assignments each held expert
        computed at the masked positions."""
        mask = batch["loss_mask"]
        x, rows = self._trunk(params, batch["tokens"], mask)
        losses = L.row_xent(self._head(params, x), batch["labels"], mask,
                            valid_vocab=self.cfg.vocab_size)
        return losses, {"moe_rows": rows}

    # ------------------------------------------------------------------
    # Serve: prefill + decode, one layer after another
    # ------------------------------------------------------------------
    def _layer_params(self, params, i):
        kind = self.cfg.layer_pattern[i]
        j = self.layers_of[kind].index(i)
        return kind, j, jax.tree.map(lambda a: a[j], params[kind])

    def prefill(self, params, batch, cache_budget: int = 0):
        c = self.cfg
        x = self._embed(params, batch["tokens"])
        s = x.shape[1]
        caches: Dict[str, list] = {"conv": [], "ssm": [], "k": [], "v": []}
        for i in range(c.n_layers):
            kind, _, p = self._layer_params(params, i)
            h = self._norm(x, p["norm"])
            if kind == "mamba":
                out, (conv, ssm) = mamba2.mixer(c, p, h)
                caches["conv"].append(conv)
                caches["ssm"].append(ssm)
            else:
                q, k, v = self._qkv(p, h, jnp.arange(s))
                out = self._out(p, L.attention_chunked(q, k, v, causal=True, scale=self.attn_scale))
                pad = ((0, 0), (0, cache_budget), (0, 0), (0, 0))
                caches["k"].append(L.cache_store(jnp.pad(k, pad)).astype(CACHE_DTYPE))
                caches["v"].append(L.cache_store(jnp.pad(v, pad)).astype(CACHE_DTYPE))
            x, _ = self._ffn(p, self._add(x, out))
        logits = self._head(params, x[:, -1:])[..., : c.vocab_size]
        return {k: jnp.stack(v) for k, v in caches.items() if v}, logits

    def decode_step(self, params, cache, batch):
        """batch: {'token': (b, 1) int32, 'pos': scalar int32}."""
        c = self.cfg
        x = self._embed(params, batch["token"])
        pos = jnp.asarray(batch["pos"])
        new = {k: list(v) for k, v in cache.items()}
        for i in range(c.n_layers):
            kind, j, p = self._layer_params(params, i)
            h = self._norm(x, p["norm"])
            if kind == "mamba":
                out, (new["conv"][j], new["ssm"][j]) = mamba2.mixer(
                    c, p, h, (cache["conv"][j], cache["ssm"][j]))
            else:
                q, k, v = self._qkv(p, h, pos[None])
                kc = L.cache_write(cache["k"][j], k, pos)
                vc = L.cache_write(cache["v"][j], v, pos)
                new["k"][j], new["v"][j] = kc, vc
                out = self._out(p, L.attention_decode(q, kc, vc, pos + 1, scale=self.attn_scale))
            x, _ = self._ffn(p, self._add(x, out))
        logits = self._head(params, x)[..., : c.vocab_size]
        return {k: jnp.stack(v) for k, v in new.items()}, logits

    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        return mamba2.Mamba2LM.input_specs(self, shape)

    def cache_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        c = self.cfg
        b, n_m, n_a = shape.global_batch, len(self.layers_of["mamba"]), len(self.layers_of["attention"])
        di, nh, g, ds, conv_dim = mamba2.mixer_dims(c)
        hd = c.resolved_head_dim
        kv = (n_a, b, c.n_kv_heads, shape.seq_len, hd)
        kv_axes = ("layers", "cache_batch", "kv_heads", "kv_seq", None)
        return {
            "conv": mod.spec((n_m, b, c.ssm_conv - 1, conv_dim),
                             ("layers", "cache_batch", None, "ssm_inner"), mamba2.STATE_DTYPE, "zeros"),
            "ssm": mod.spec((n_m, b, nh, c.ssm_headdim, ds),
                            ("layers", "cache_batch", "ssm_heads", None, "state"),
                            mamba2.STATE_DTYPE, "zeros"),
            "k": mod.spec(kv, kv_axes, CACHE_DTYPE, "zeros"),
            "v": mod.spec(kv, kv_axes, CACHE_DTYPE, "zeros"),
        }
