"""Mixture-of-Experts layers: a capacity-based one that may drop tokens, and
a dropless one for a chip that holds a share of the experts
(``moe_share``). Both route with ``_route``.

The capacity layer has two dispatch implementations:

  * ``dispatch="scatter"`` (default) — positions computed by cumsum over the
    routing one-hots, tokens moved with scatter-add / gather. O(tokens * d)
    data movement, no O(tokens^2) matmul. Gradients are the dual
    gather/scatter, equally cheap.
  * ``dispatch="einsum"`` — the classic mesh-TF / MaxText one-hot-matmul
    formulation. O(tokens * E*C * d) per group: measured ~8x the expert
    FFN compute itself on mixtral-8x22b train_4k (see EXPERIMENTS.md §Perf —
    kept as the measured baseline of that hillclimb step).

Expert FFN weights are sharded ``mlp -> model``; the expert dim is guarded
(8 / 40 experts do not divide the 16-way model axis — DESIGN.md
§Arch-applicability).
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.launch.sharding import logical_constraint

# (rows, contraction, output) tile of the grouped matmul
GMM_TILING = (512, 1024, 1024)


def moe_capacity(tokens_per_group: int, top_k: int, n_experts: int, cf: float) -> int:
    c = math.ceil(tokens_per_group * top_k * cf / n_experts)
    return max(4, min(c, tokens_per_group * top_k))


def _route(x, router_w, top_k):
    """Returns (gate (b,s,k) f32, idx (b,s,k) i32, aux scalar)."""
    e = router_w.shape[-1]
    # logits in float32: rounded to a bfloat16 input's type, near-tied
    # experts would swap on the last bit of the sum, which depends on the
    # batch's shape
    logits = jnp.einsum("bsd,de->bse", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    density = jnp.mean(probs, axis=(0, 1))
    frac = jnp.mean(jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(density * frac)
    return gate, idx, aux


def _positions(idx, e, top_k, cap):
    """Capacity slots per (token, k): (pos (b,t), keep (b,t)) with t = s*k."""
    b, s, k = idx.shape
    oh = jax.nn.one_hot(idx, e, dtype=jnp.float32).reshape(b, s * k, e)
    pos_in_e = jnp.cumsum(oh, axis=1) - oh
    pos = jnp.sum(pos_in_e * oh, axis=-1)  # (b, t)
    keep = pos < cap
    return pos.astype(jnp.int32), keep


def _expert_ffn(dispatched, w_gate, w_up, w_down):
    # dispatched: batch over data, d replicated — the d-contraction is local
    # and becf comes out f-sharded from the weights (no per-matmul psum)
    dispatched = logical_constraint(dispatched, ("batch", "expert", None, "embed"))
    h = jnp.einsum("becd,edf->becf", dispatched, w_gate.astype(dispatched.dtype))
    u = jnp.einsum("becd,edf->becf", dispatched, w_up.astype(dispatched.dtype))
    h = logical_constraint(h, ("batch", "expert", None, "mlp"))
    h = jax.nn.silu(h.astype(jnp.float32)).astype(dispatched.dtype) * u
    out = jnp.einsum("becf,efd->becd", h, w_down.astype(dispatched.dtype))
    return logical_constraint(out, ("batch", "expert", None, "embed"))


def moe_layer(
    x: jax.Array,  # (b, s, d)
    router_w: jax.Array,  # (d, E)
    w_gate: jax.Array,  # (E, d, f)
    w_up: jax.Array,
    w_down: jax.Array,  # (E, f, d)
    top_k: int,
    capacity_factor: float = 1.25,
    dispatch: str = "scatter",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (b, s, d), aux_loss scalar)."""
    b, s, d = x.shape
    e = router_w.shape[-1]
    cap = moe_capacity(s, top_k, e, capacity_factor)
    gate, idx, aux = _route(x, router_w, top_k)

    if dispatch == "einsum":
        return _moe_einsum(x, gate, idx, aux, w_gate, w_up, w_down, cap, e, top_k)

    pos, keep = _positions(idx, e, top_k, cap)  # (b, t)
    t = s * top_k
    idx_f = idx.reshape(b, t)
    gate_f = gate.reshape(b, t) * keep
    x_rep = jnp.broadcast_to(x[:, :, None, :], (b, s, top_k, d)).reshape(b, t, d)
    val = x_rep * keep[..., None].astype(x.dtype)

    # vmap over the (data-sharded) batch dim so the scatter/gather carry an
    # explicit batching dim — the SPMD partitioner keeps them batch-local
    # instead of replicating (global-index scatter forces all-gathers).
    def scatter_one(vv, ii, pp):
        return jnp.zeros((e, cap, d), x.dtype).at[ii, pp].add(vv, mode="drop")

    dispatched = jax.vmap(scatter_one)(val, idx_f, pos)

    expert_out = _expert_ffn(dispatched, w_gate, w_up, w_down)

    gathered = jax.vmap(lambda eo, ii, pp: eo[ii, pp])(expert_out, idx_f, pos)
    out = (gathered * gate_f[..., None].astype(x.dtype)).reshape(b, s, top_k, d).sum(axis=2)
    return out, aux.astype(jnp.float32)


def _moe_einsum(x, gate, idx, aux, w_gate, w_up, w_down, cap, e, top_k):
    b, s, d = x.shape
    t = s * top_k
    pos, keep = _positions(idx, e, top_k, cap)
    oh_f = jax.nn.one_hot(idx, e, dtype=jnp.float32).reshape(b, t, e)
    gate_f = gate.reshape(b, t) * keep
    cap_oh = jax.nn.one_hot(pos, cap, dtype=x.dtype)
    disp = (oh_f * keep[..., None]).astype(x.dtype)
    x_rep = jnp.broadcast_to(x[:, :, None, :], (b, s, top_k, d)).reshape(b, t, d)
    dispatched = jnp.einsum("bte,btc,btd->becd", disp, cap_oh, x_rep)
    expert_out = _expert_ffn(dispatched, w_gate, w_up, w_down)
    combined = jnp.einsum(
        "becd,bte,btc,bt->btd", expert_out, disp, cap_oh, gate_f.astype(x.dtype)
    )
    out = combined.reshape(b, s, top_k, d).sum(axis=2)
    return out, aux.astype(jnp.float32)


def _tile(dim: int, target: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``target``; ``dim`` itself when none is."""
    return next((t for t in range(target - target % 128, 0, -128) if dim % t == 0), dim)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``lhs`` (m, k) rows sorted by group times ``rhs`` (g, k, n), each row
    by its group's matrix: megablox's grouped matmul, one Pallas kernel
    whose grid covers the row tiles of the groups' ``sum(group_sizes)`` rows
    and no more. Rows past them come back unwritten. ``m`` must be a
    multiple of the row tile; the other two tiles are fitted to divide
    ``k`` and ``n``. The Pallas interpreter stands in for the chip
    on the CPU."""
    k, n = rhs.shape[1:]
    tiling = (GMM_TILING[0], _tile(k, GMM_TILING[1]), _tile(n, GMM_TILING[2]))

    def call(on_cpu, lhs, rhs, group_sizes):
        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, False, on_cpu)

    return jax.lax.platform_dependent(
        lhs, rhs, group_sizes,
        cpu=lambda *xs: call(True, *xs), default=lambda *xs: call(False, *xs))


def share_bounds(tokens: int, top_k: int, held: int, experts: int) -> Tuple[int, int]:
    """Static row bounds of ``moe_share``, in whole row tiles: ``(fast,
    full)``. ``full`` holds any routing (a token holds at most
    ``min(top_k, held)`` assignments to held experts); ``fast`` holds twice
    the ``tokens * top_k * held / experts`` assignments a balanced router
    sends to them, and is what almost every step uses."""
    tm = GMM_TILING[0]
    full = -(-tokens * min(top_k, held) // tm) * tm
    fast = -(-2 * tokens * top_k * held // (experts * tm)) * tm
    return min(fast, full), full


def moe_share(
    x: jax.Array,  # (b, s, d)
    router_w: jax.Array,  # (d, E)
    w_gate_up: jax.Array,  # (H, d, 2f): gate columns, then up columns
    w_down: jax.Array,  # (H, f, d)
    held: Sequence[int],  # the H expert ids this chip holds, of E
    top_k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Dropless expert layer of a chip that holds ``held`` of the router's
    experts: routes over all of them (top ``top_k``, gates a softmax over
    the chosen), and adds ``gate * expert(x)`` for every assignment to a
    held expert, ``expert(h) = w_down(silu(h w_gate) * h w_up)``; the gates
    are not renormalised over the held experts. Every such assignment is
    computed, so a token's output depends on no other token.

    The held assignments, sorted by expert, are gathered into a static
    number of rows (``share_bounds``): the fast bound when they fit, else
    the full one, chosen on the device. Rows past them are zeros, the
    grouped matmul's grid covers only the groups' rows, and each row's
    gated output is added back to its token with one segment sum.

    Returns ``(out (b, s, d), slots (b, s, top_k))``: each assignment's
    local expert index, ``H`` where the expert is not held."""
    b, s, d = x.shape
    e, h, f = router_w.shape[-1], len(held), w_down.shape[1]
    gate, idx, _ = _route(x, router_w, top_k)
    local = np.full(e, h, np.int32)  # expert id -> local index, h where not held
    local[list(held)] = np.arange(h)
    slots = jnp.asarray(local)[idx]
    t = b * s
    flat = slots.reshape(-1)
    order = jnp.argsort(flat, stable=True)  # held assignments first, by expert
    sizes = jnp.sum(flat[:, None] == jnp.arange(h, dtype=jnp.int32), axis=0, dtype=jnp.int32)
    total = jnp.sum(sizes)
    xf, gates = x.reshape(t, d), gate.reshape(-1)
    w_gate_up, w_down = w_gate_up.astype(x.dtype), w_down.astype(x.dtype)

    def rows_of(bound):
        def run(_):
            rows = jnp.pad(order, (0, max(0, bound - order.size)))[:bound]
            # the kernel leaves rows past the groups unwritten, in its
            # gradient too: zeroing them where they enter and leave keeps
            # them out of both directions
            real = (jnp.arange(bound) < total)[:, None]
            hu = grouped_matmul(jnp.where(real, xf[rows // top_k], 0), w_gate_up, sizes)
            hu = jnp.where(real, hu, 0)
            hh = jax.nn.silu(hu[:, :f].astype(jnp.float32)).astype(x.dtype) * hu[:, f:]
            y = jnp.where(real, grouped_matmul(hh, w_down, sizes), 0)
            y = y * gates[rows][:, None].astype(y.dtype)
            return jax.ops.segment_sum(y, rows // top_k, num_segments=t)
        return run

    fast, full = share_bounds(t, top_k, h, e)
    out = (rows_of(full)(None) if fast == full
           else jax.lax.cond(total <= fast, rows_of(fast), rows_of(full), None))
    return out.reshape(b, s, d), slots
