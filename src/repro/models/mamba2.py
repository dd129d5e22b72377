"""Mamba2 (SSD — state-space duality) LM [arXiv:2405.21060].

Implements the chunked SSD algorithm: intra-chunk quadratic attention-like
term + inter-chunk linear state recurrence. ``ssd_chunked`` is the pure-jnp
formulation (also the oracle for the Pallas kernel in
``repro.kernels.ssd_scan``); decode keeps an O(1) recurrent state, which is
what makes the ``long_500k`` cell feasible for this family.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.sharding import logical_constraint
from repro.models import layers as L
from repro.models import module as mod
from repro.models.transformer import remat_wrap

STATE_DTYPE = jnp.float32


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x (b, s, ch), w (ch, k), b (ch,)."""
    k = w.shape[-1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + pad[:, i : i + x.shape[1]].astype(jnp.float32) * w[:, i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum a[..., j+1..i], -inf for j > i.

    a: (..., q). returns (..., q, q) lower-triangular log-decay matrix.
    """
    q = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)  # (..., q)
    diff = cum[..., :, None] - cum[..., None, :]  # (..., i, j) = sum(j+1..i)
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # (b, s, nh, hd)
    dt: jax.Array,  # (b, s, nh)   (already softplus'ed, > 0)
    a_log: jax.Array,  # (nh,)     A = -exp(a_log)
    b_mat: jax.Array,  # (b, s, g, ds)
    c_mat: jax.Array,  # (b, s, g, ds)
    chunk: int,
    init_state: jax.Array | None = None,  # (b, nh, hd, ds)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (b, s, nh, hd), final_state (b, nh, hd, ds))."""
    bsz, s, nh, hd = x.shape
    g, ds = b_mat.shape[2], b_mat.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = nh // g

    A = -jnp.exp(a_log.astype(jnp.float32))  # (nh,)
    dt32 = dt.astype(jnp.float32)
    da = dt32 * A  # (b, s, nh) log-decay per step

    xr = x.reshape(bsz, nc, chunk, nh, hd).astype(jnp.float32)
    dtr = dt32.reshape(bsz, nc, chunk, nh)
    dar = da.reshape(bsz, nc, chunk, nh)
    br = jnp.repeat(b_mat.reshape(bsz, nc, chunk, g, ds), rep, axis=3).astype(jnp.float32)
    cr = jnp.repeat(c_mat.reshape(bsz, nc, chunk, g, ds), rep, axis=3).astype(jnp.float32)

    # --- intra-chunk (quadratic within chunk) ---
    lmat = jnp.exp(segsum(dar.transpose(0, 1, 3, 2)))  # (b, nc, nh, q, q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", cr, br) * lmat
    scores = scores * dtr.transpose(0, 1, 3, 2)[:, :, :, None, :]  # weight by dt_j
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores, xr)

    # --- chunk states ---
    cum = jnp.cumsum(dar, axis=2)  # (b, nc, q, nh)
    total = cum[:, :, -1]  # (b, nc, nh)
    decay_to_end = jnp.exp(total[:, :, None] - cum)  # (b, nc, q, nh)
    s_chunk = jnp.einsum(
        "bcqhn,bcqh,bcqhp->bchpn", br, dtr * decay_to_end, xr
    )  # (b, nc, nh, hd, ds)

    # --- inter-chunk recurrence over chunk states ---
    h0 = (
        jnp.zeros((bsz, nh, hd, ds), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def step(h, inp):
        s_c, tot = inp  # (b, nh, hd, ds), (b, nh)
        h_prev = h
        h = h * jnp.exp(tot)[:, :, None, None] + s_c
        return h, h_prev

    final, h_prevs = jax.lax.scan(
        step,
        h0,
        (s_chunk.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)),
    )
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)  # (b, nc, nh, hd, ds)

    # --- inter-chunk contribution ---
    in_decay = jnp.exp(cum)  # (b, nc, q, nh)
    y_inter = jnp.einsum("bcqhn,bcqh,bchpn->bcqhp", cr, in_decay, h_prevs)

    y = (y_intra + y_inter).reshape(bsz, s, nh, hd)
    return y, final


def ssd_decode_step(
    state: jax.Array,  # (b, nh, hd, ds) fp32
    x: jax.Array,  # (b, nh, hd)
    dt: jax.Array,  # (b, nh)
    a_log: jax.Array,  # (nh,)
    b_vec: jax.Array,  # (b, g, ds)
    c_vec: jax.Array,  # (b, g, ds)
) -> Tuple[jax.Array, jax.Array]:
    nh = x.shape[1]
    g = b_vec.shape[1]
    rep = nh // g
    A = -jnp.exp(a_log.astype(jnp.float32))
    da = jnp.exp(dt.astype(jnp.float32) * A)  # (b, nh)
    br = jnp.repeat(b_vec, rep, axis=1).astype(jnp.float32)  # (b, nh, ds)
    cr = jnp.repeat(c_vec, rep, axis=1).astype(jnp.float32)
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dt.astype(jnp.float32), x.astype(jnp.float32), br)
    state = state * da[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, cr)
    return state, y


def mixer_dims(cfg: ModelConfig):
    """``(d_inner, heads, groups, state, conv channels)`` of a Mamba-2 mixer."""
    di = cfg.d_inner
    g, ds = cfg.ssm_ngroups, cfg.ssm_state
    return di, cfg.ssm_nheads, g, ds, di + 2 * g * ds


def mixer_specs(cfg: ModelConfig, nl: int) -> Dict[str, mod.ParamSpec]:
    """Parameters of ``nl`` stacked Mamba-2 mixers, their pre-norm included."""
    d = cfg.d_model
    di, nh, g, ds, conv_dim = mixer_dims(cfg)
    proj_out = 2 * di + 2 * g * ds + nh
    return {
        "norm": mod.spec((nl, d), ("layers", "embed"), init="ones"),
        "w_in": mod.spec((nl, d, proj_out), ("layers", "embed", "ssm_inner"), init="scaled"),
        "conv_w": mod.spec((nl, conv_dim, cfg.ssm_conv), ("layers", "ssm_inner", "conv"), init="scaled"),
        "conv_b": mod.spec((nl, conv_dim), ("layers", "ssm_inner"), init="zeros"),
        "dt_bias": mod.spec((nl, nh), ("layers", "ssm_heads"), init="zeros"),
        "a_log": mod.spec((nl, nh), ("layers", "ssm_heads"), init="zeros"),
        "d_skip": mod.spec((nl, nh), ("layers", "ssm_heads"), init="ones"),
        "norm_g": mod.spec((nl, di), ("layers", "ssm_inner"), init="ones"),
        "w_out": mod.spec((nl, di, d), ("layers", "ssm_inner", "embed"), init="scaled"),
    }


def mixer(cfg: ModelConfig, p, h, state=None):
    """The Mamba-2 mixer on normed input ``h`` (b, s, d): in-projection,
    causal conv, SSD, ``rmsnorm(y * silu(z))``, out-projection. Returns
    ``(out, (conv_state, ssm_state))``: with ``state`` None the whole
    sequence runs through the chunked scan and the states are those after its
    last position; with ``state`` one position steps the recurrence."""
    di, nh, g, ds, conv_dim = mixer_dims(cfg)
    zxbcdt = jnp.einsum("bsd,dp->bsp", h, p["w_in"].astype(h.dtype))
    z = zxbcdt[..., :di]
    xbc_raw = zxbcdt[..., di : di + conv_dim]
    dt = zxbcdt[..., di + conv_dim :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    if state is None:
        xbc = causal_conv1d(xbc_raw, p["conv_w"], p["conv_b"])
        xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(h.dtype)
        x_in = xbc[..., :di].reshape(*xbc.shape[:2], nh, cfg.ssm_headdim)
        b_mat = xbc[..., di : di + g * ds].reshape(*xbc.shape[:2], g, ds)
        c_mat = xbc[..., di + g * ds :].reshape(*xbc.shape[:2], g, ds)
        y, final = ssd_chunked(x_in, dt, p["a_log"], b_mat, c_mat, cfg.ssm_chunk)
        y = y + p["d_skip"].astype(jnp.float32)[:, None] * x_in.astype(jnp.float32)
        y = y.reshape(*xbc.shape[:2], di)
        new_state = (xbc_raw[:, -(cfg.ssm_conv - 1):].astype(STATE_DTYPE), final)
    else:
        conv_state, ssm_state = state  # (b, conv-1, conv_dim), (b, nh, hd, ds)
        seq = jnp.concatenate([conv_state.astype(xbc_raw.dtype), xbc_raw], axis=1)
        conv_out = causal_conv1d(seq, p["conv_w"], p["conv_b"])[:, -1:]
        xbc1 = jax.nn.silu(conv_out.astype(jnp.float32)).astype(h.dtype)[:, 0]
        x_in = xbc1[..., :di].reshape(-1, nh, cfg.ssm_headdim)
        b_vec = xbc1[..., di : di + g * ds].reshape(-1, g, ds)
        c_vec = xbc1[..., di + g * ds :].reshape(-1, g, ds)
        ssm_state, y = ssd_decode_step(ssm_state, x_in, dt[:, 0], p["a_log"], b_vec, c_vec)
        y = y + p["d_skip"].astype(jnp.float32)[:, None] * x_in.astype(jnp.float32)
        y = y.reshape(h.shape[0], 1, di)
        new_state = (seq[:, 1:].astype(conv_state.dtype), ssm_state)

    # gated RMSNorm then out-projection
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = L.rms_norm(y.astype(h.dtype), p["norm_g"], cfg.norm_eps)
    return jnp.einsum("bsd,dp->bsp", y, p["w_out"].astype(h.dtype)), new_state


class Mamba2LM:
    def __init__(self, cfg: ModelConfig, remat_policy: str = "full"):
        self.cfg = cfg
        self.remat_policy = remat_policy

    # ------------------------------------------------------------------
    def param_specs(self):
        c = self.cfg
        p: Dict[str, Any] = {
            "embed": mod.spec((c.padded_vocab, c.d_model), ("vocab", "embed")),
            "layers": mixer_specs(c, c.n_layers),
            "final_norm": mod.spec((c.d_model,), ("embed",), init="ones"),
        }
        if not c.tie_embeddings:
            p["head"] = mod.spec((c.d_model, c.padded_vocab), ("embed", "vocab"), init="scaled")
        return p

    def init_params(self, key):
        return mod.init_tree(self.param_specs(), key)

    # ------------------------------------------------------------------
    def _block(self, p, x, state=None):
        """Pre-norm residual block: ``(x + mixer(rmsnorm(x)), states)``."""
        h = L.rms_norm(x, p["norm"], self.cfg.norm_eps)
        out, new_state = mixer(self.cfg, p, h, state)
        return logical_constraint(x + out, ("batch", "seq", "embed")), new_state

    def _head(self, params, x):
        x = L.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        return L.lm_logits(x, head)

    def logits(self, params, tokens):
        """Full-sequence logits (b, s, padded vocab)."""
        x = L.embed(tokens, params["embed"])
        x = logical_constraint(x, ("batch", "seq", "embed"))
        block = remat_wrap(lambda xx, pp: self._block(pp, xx)[0], self.remat_policy)
        x, _ = jax.lax.scan(lambda xx, pp: (block(xx, pp), None), x, params["layers"])
        return logical_constraint(self._head(params, x), ("batch", "seq", "vocab"))

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

    # ------------------------------------------------------------------
    def prefill(self, params, batch, cache_budget: int = 0):
        # recurrent state is O(1): no budget needed
        x = L.embed(batch["tokens"], params["embed"])
        block = remat_wrap(lambda xx, pp: self._block(pp, xx), self.remat_policy)
        x, states = jax.lax.scan(lambda xx, pp: block(xx, pp), x, params["layers"])
        logits = self._head(params, x[:, -1:])[..., : self.cfg.vocab_size]
        conv_states, ssm_states = states
        return {"conv": conv_states, "ssm": ssm_states}, logits

    def decode_step(self, params, cache, batch):
        x = L.embed(batch["token"], params["embed"])

        def scan_body(xx, per_layer):
            pp, conv_s, ssm_s = per_layer
            return self._block(pp, xx, (conv_s, ssm_s))

        x, (conv_n, ssm_n) = jax.lax.scan(
            scan_body, x, (params["layers"], cache["conv"], cache["ssm"])
        )
        logits = self._head(params, x)[..., : self.cfg.vocab_size]
        return {"conv": conv_n, "ssm": ssm_n}, logits

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
        di, nh, g, ds, conv_dim = mixer_dims(c)
        return {
            "conv": mod.spec(
                (c.n_layers, b, c.ssm_conv - 1, conv_dim),
                ("layers", "cache_batch", None, "ssm_inner"),
                STATE_DTYPE, "zeros",
            ),
            "ssm": mod.spec(
                (c.n_layers, b, nh, c.ssm_headdim, ds),
                ("layers", "cache_batch", "ssm_heads", None, "state"),
                STATE_DTYPE, "zeros",
            ),
        }
