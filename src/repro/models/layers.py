"""Shared model layers: norms, RoPE, GQA attention (chunked online-softmax),
MLPs, embeddings, losses.

Attention notes (TPU adaptation):
  * ``attention_chunked`` is a flash-attention-equivalent formulation in pure
    ``jax.lax`` (scan over KV chunks with online softmax). It never
    materialises the full (sq, skv) score matrix, so prefill_32k compiles and
    fits; on real TPUs the Pallas kernel in ``repro.kernels.flash_attention``
    is the fast path (selected via ``use_pallas``).
  * ``attention_decode`` is a single-token dense attention over the KV cache.
    When the cache is sharded over ``kv_seq`` (mesh axis ``model``), XLA's
    SPMD partitioner turns the softmax/contraction reductions into
    flash-decoding-style partial reductions + all-reduces.
  * GQA: caches store ``n_kv_heads`` heads; KV is repeated to ``n_heads``
    per chunk at compute time (chunk-local, negligible memory).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch.sharding import logical_constraint

COMPUTE_DTYPE = jnp.bfloat16

# (batch, kv_heads, group, query_seq[, head_dim]) — the flash-attention
# working layout. kv_heads never divides the 16-way model axis on the
# assigned archs, so the divisibility guard routes `model` to the query dim.
_QS_AXES = ("batch", "kv_heads", None, "attn_sq", None)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim//2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(b, s, hkv, d) -> (b, s, n_heads, d) by group broadcast."""
    b, s, hkv, d = k.shape
    g = n_heads // hkv
    if g == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, g, d))
    return k.reshape(b, s, n_heads, d)


def _chunk_mask(sq: int, skv: int, chunk: int, c_idx, causal: bool,
                window: Optional[int], q_offset: int):
    """(sq, chunk) validity mask for kv chunk c_idx."""
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = (c_idx * chunk + jnp.arange(chunk))[None, :]
    mask = k_pos < skv  # padded keys
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


def _flash_fwd_scan(q5, k, v, causal, window, chunk, q_offset, scale=None):
    """Online-softmax forward. q5: (b, sq, hkv, g, hd); k/v: (b, skv, hkv, hd).
    Scores are scaled by ``scale`` (default 1/sqrt(hd)).

    Returns out5 (b, sq, hkv, g, hd) and lse (b, hkv, g, sq) fp32.
    """
    b, sq, hkv, g, hd = q5.shape
    skv = k.shape[1]
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (skv + pad) // chunk
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qs = (q5.astype(COMPUTE_DTYPE) * scale).transpose(0, 2, 3, 1, 4)  # (b,k,g,sq,hd)
    qs = logical_constraint(qs, _QS_AXES)
    k_sc = k.reshape(b, n_chunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    v_sc = v.reshape(b, n_chunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        m, l, acc = carry  # (b,k,g,sq), (b,k,g,sq), (b,k,g,sq,hd)
        k_c, v_c, c_idx = inp
        s = jnp.einsum(
            "bkgqd,bckd->bkgqc", qs, k_c.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32,
        )
        mask = _chunk_mask(sq, skv, chunk, c_idx, causal, window, q_offset)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p.astype(COMPUTE_DTYPE), v_c.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = logical_constraint(jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32), _QS_AXES[:4])
    l0 = logical_constraint(jnp.zeros((b, hkv, g, sq), jnp.float32), _QS_AXES[:4])
    acc0 = logical_constraint(jnp.zeros((b, hkv, g, sq, hd), jnp.float32), _QS_AXES)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (k_sc, v_sc, jnp.arange(n_chunks)))
    l_safe = jnp.maximum(l, 1e-20)
    out = (acc / l_safe[..., None]).transpose(0, 3, 1, 2, 4)  # (b,sq,k,g,hd)
    lse = m + jnp.log(l_safe)
    return out.astype(q5.dtype), lse


from functools import partial as _partial  # noqa: E402


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q5, k, v, causal=True, window=None, chunk=1024, q_offset=0, scale=None):
    """Flash attention (pure-jax custom_vjp): saves only (out, lse); the
    backward re-streams KV chunks — no O(sq*skv) tensor is ever saved.
    GQA-native: q5 (b, sq, hkv, g, hd) against k/v (b, skv, hkv, hd)."""
    out, _ = _flash_fwd_scan(q5, k, v, causal, window, chunk, q_offset, scale)
    return out


def _flash_fwd(q5, k, v, causal, window, chunk, q_offset, scale):
    out, lse = _flash_fwd_scan(q5, k, v, causal, window, chunk, q_offset, scale)
    return out, (q5, k, v, out, lse)


def _flash_bwd(causal, window, chunk, q_offset, scale, res, d_out):
    q5, k, v, out, lse = res
    b, sq, hkv, g, hd = q5.shape
    skv = k.shape[1]
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    n_chunks = (skv + pad) // chunk
    scale = 1.0 / math.sqrt(hd) if scale is None else scale

    qs = logical_constraint(q5.astype(COMPUTE_DTYPE).transpose(0, 2, 3, 1, 4), _QS_AXES)
    do = logical_constraint(d_out.astype(COMPUTE_DTYPE).transpose(0, 2, 3, 1, 4), _QS_AXES)
    o5 = out.astype(COMPUTE_DTYPE).transpose(0, 2, 3, 1, 4)
    delta = jnp.sum(do.astype(jnp.float32) * o5.astype(jnp.float32), axis=-1)  # (b,k,g,sq)
    k_sc = kp.reshape(b, n_chunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    v_sc = vp.reshape(b, n_chunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)

    def body(dq_acc, inp):
        k_c, v_c, c_idx = inp
        s = jnp.einsum(
            "bkgqd,bckd->bkgqc", qs * scale, k_c.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32,
        )
        mask = _chunk_mask(sq, skv, chunk, c_idx, causal, window, q_offset)
        p = jnp.where(mask[None, None, None], jnp.exp(s - lse[..., None]), 0.0)
        pc = p.astype(COMPUTE_DTYPE)
        dv_c = jnp.einsum("bkgqc,bkgqd->bckd", pc, do, preferred_element_type=jnp.float32)
        dp = jnp.einsum(
            "bkgqd,bckd->bkgqc", do, v_c.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32,
        )
        dsc = (p * (dp - delta[..., None])).astype(COMPUTE_DTYPE)
        dq_c = jnp.einsum(
            "bkgqc,bckd->bkgqd", dsc, k_c.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32,
        )
        dk_c = jnp.einsum("bkgqc,bkgqd->bckd", dsc, qs, preferred_element_type=jnp.float32)
        return dq_acc + dq_c, (dk_c * scale, dv_c)

    dq0 = logical_constraint(jnp.zeros((b, hkv, g, sq, hd), jnp.float32), _QS_AXES)
    dq, (dk_st, dv_st) = jax.lax.scan(body, dq0, (k_sc, v_sc, jnp.arange(n_chunks)))
    dq5 = (dq * scale).transpose(0, 3, 1, 2, 4).astype(q5.dtype)
    dk = dk_st.transpose(1, 0, 2, 3, 4).reshape(b, skv + pad, hkv, hd)[:, :skv]
    dv = dv_st.transpose(1, 0, 2, 3, 4).reshape(b, skv + pad, hkv, hd)[:, :skv]
    return dq5, dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def attention_chunked(
    q: jax.Array,  # (b, sq, hq, hd)
    k: jax.Array,  # (b, skv, hkv, hd)
    v: jax.Array,  # (b, skv, hkv, hd)
    q_offset: int = 0,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Flash-attention wrapper at (b, s, heads, hd) layout (GQA handled
    natively inside — KV is never repeated at full sequence length).
    Scores are scaled by ``scale`` (default 1/sqrt(hd))."""
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    q5 = q.reshape(b, sq, hkv, hq // hkv, hd)
    out5 = flash_attention(q5, k, v, causal, window, chunk, q_offset, scale)
    return out5.reshape(b, sq, hq, hd)


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, q_offset: int = 0,
    causal: bool = True, window: Optional[int] = None,
) -> jax.Array:
    """Materialized-softmax oracle for tests."""
    b, sq, hq, hd = q.shape
    skv = k.shape[1]
    k_r = _repeat_kv(k, hq).astype(jnp.float32)
    v_r = _repeat_kv(v, hq).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k_r) / math.sqrt(hd)
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v_r)
    return out.astype(q.dtype)


def attention_decode(
    q: jax.Array,  # (b, 1, hq, hd)
    k_cache: jax.Array,  # (b, hkv, skv, hd) — attention-native layout
    v_cache: jax.Array,
    cache_len: jax.Array,  # (b,) or scalar — number of valid cache entries
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token attention over a pre-allocated cache.

    GQA-native (KV never repeated) and layout-native (the cache is stored
    (b, hkv, skv, hd) so the QK^T / PV contractions need no transposes).
    When the cache is sharded over ``kv_seq`` (mesh axis ``model``), XLA
    partitions the max/sum/PV reductions into flash-decoding-style partial
    reductions + small all-reduces.
    """
    b, _, hq, hd = q.shape
    hkv, skv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    # f32 at slice level: decode is HBM-bound, and explicit casts avoid the
    # CPU backend's whole-cache bf16->f32 operand mirror (see decode_attn.py)
    qc = (q.astype(jnp.float32) * scale)[:, 0].reshape(b, hkv, g, hd)
    s = jnp.einsum("bkgd,bksd->bkgs", qc, k_cache.astype(jnp.float32))
    pos = jnp.arange(skv)[None, :]  # (1, skv)
    cl = jnp.asarray(cache_len).reshape(-1, 1)  # (b or 1, 1)
    mask = pos < cl
    if window is not None:
        mask &= pos >= (cl - window)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bkgs,bksd->bkgd", p / jnp.maximum(l, 1e-20), v_cache.astype(jnp.float32)
    )
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def cache_store(k: jax.Array) -> jax.Array:
    """(b, s, hkv, hd) -> cache layout (b, hkv, s, hd)."""
    return k.transpose(0, 2, 1, 3)


def cache_write(cache: jax.Array, new: jax.Array, slot) -> jax.Array:
    """Write ``new`` (b, 1, hkv, hd) into cache (b, hkv, A, hd) at ``slot``."""
    return jax.lax.dynamic_update_slice_in_dim(
        cache, cache_store(new).astype(cache.dtype), slot, axis=2
    )


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_swiglu(x, w_gate, w_up, w_down):
    h = jnp.einsum("bsd,df->bsf", x, w_gate.astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, w_up.astype(x.dtype))
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, w_down.astype(x.dtype))


def mlp_gelu(x, w_up, w_down, b_up=None, b_down=None):
    h = jnp.einsum("bsd,df->bsf", x, w_up.astype(x.dtype))
    if b_up is not None:
        h = h + b_up.astype(h.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    out = jnp.einsum("bsf,fd->bsd", h, w_down.astype(x.dtype))
    if b_down is not None:
        out = out + b_down.astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    """tokens (b, s) int32 -> (b, s, d). Gather; XLA partitions sharded vocab."""
    return jnp.take(table, tokens, axis=0).astype(COMPUTE_DTYPE)


def lm_logits(x: jax.Array, head: jax.Array) -> jax.Array:
    """x (b, s, d) @ head (d, vocab) -> (b, s, vocab)."""
    return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))


def token_xent(logits: jax.Array, labels: jax.Array,
               valid_vocab: Optional[int] = None) -> jax.Array:
    """Next-token cross entropy at each position (b, s), in float32.

    ``valid_vocab`` masks padded vocab columns (vocab padded for sharding).
    """
    logits32 = logits.astype(jnp.float32)
    if valid_vocab is not None and valid_vocab < logits.shape[-1]:
        col = jax.lax.broadcasted_iota(jnp.int32, logits32.shape, logits32.ndim - 1)
        logits32 = jnp.where(col < valid_vocab, logits32, -jnp.inf)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    label_logit = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    return lse - label_logit


def row_xent(logits: jax.Array, labels: jax.Array, mask: jax.Array,
             valid_vocab: Optional[int] = None) -> jax.Array:
    """Each row's mean cross entropy over its masked positions (b,)."""
    mask = mask.astype(jnp.float32)
    nll = token_xent(logits, labels, valid_vocab)
    return jnp.sum(nll * mask, axis=-1) / jnp.maximum(jnp.sum(mask, axis=-1), 1.0)


def softmax_xent(
    logits: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array] = None,
    valid_vocab: Optional[int] = None,
):
    """Mean next-token cross entropy. logits (b, s, v) / labels (b, s)."""
    nll = token_xent(logits, labels, valid_vocab)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def update_cache(cache: jax.Array, new: jax.Array, index: jax.Array) -> jax.Array:
    """Write ``new`` (b, 1, h, d) into ``cache`` (b, S, h, d) at ``index``."""
    return jax.lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype), index, axis=1)
