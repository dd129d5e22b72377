"""Pallas TPU kernel: MinHash signatures.

Tiling: grid (D/BD, P/BP); each program loads a (BD, S) tile of shingle
hashes + a (1, BP) slice of permutation params into VMEM and computes the
running min over the shingle axis in chunks sliced off the refs, so the
(BD, BP, CHUNK) intermediate stays VMEM-resident (64x128x256 x 4 B = 8 MiB).
Pure integer VPU work — no MXU — which is why dedup's signature stage maps
cleanly onto TPU even though the paper ran it on CPU clusters.

The TPU compiler has no unsigned-integer reductions, so the min runs in an
order-preserving signed encoding: ``u ^ 0x80000000`` bitcast to int32 sorts
exactly as ``u`` does as uint32, and the same flip decodes the result.

On the CPU backend the kernel runs in the Pallas interpreter; on every other
backend it is compiled. The choice is made when the call is lowered, for the
platform it is lowered for, so no caller picks a mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 64
BLOCK_P = 128  # lane-dim blocks on TPU must be a multiple of 128
CHUNK_S = 256

_SIGN = 0x80000000  # plain ints: jnp constants may not be captured by kernels
_SENTINEL_S = 0x7FFFFFFF  # 0xFFFFFFFF ^ _SIGN, as int32


def _minhash_kernel(h_ref, mask_ref, a_ref, b_ref, out_ref):
    a = a_ref[...][:, :, None]  # (1, BP, 1) uint32
    b = b_ref[...][:, :, None]
    bd, s = h_ref.shape
    bp = a.shape[1]
    acc = jnp.full((bd, bp), _SENTINEL_S, jnp.int32)
    for lo in range(0, s, CHUNK_S):  # static unroll: S is a compile-time shape
        w = min(CHUNK_S, s - lo)
        hc = h_ref[:, lo:lo + w]  # (BD, w) uint32
        mc = mask_ref[:, lo:lo + w]  # (BD, w) bool
        vals = a * hc[:, None, :] + b  # (BD, BP, w), u32 wrap
        vals = jax.lax.bitcast_convert_type(vals ^ jnp.uint32(_SIGN), jnp.int32)
        vals = jnp.where(mc[:, None, :], vals, _SENTINEL_S)
        acc = jnp.minimum(acc, vals.min(axis=2))
    out_ref[...] = jax.lax.bitcast_convert_type(acc, jnp.uint32) ^ jnp.uint32(_SIGN)


def _call(h, mask, a, b, *, on_cpu: bool):
    d, s = h.shape
    p = a.shape[0]
    return pl.pallas_call(
        _minhash_kernel,
        grid=(d // BLOCK_D, p // BLOCK_P),
        in_specs=[
            pl.BlockSpec((BLOCK_D, s), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_D, s), lambda i, j: (i, 0)),
            pl.BlockSpec((1, BLOCK_P), lambda i, j: (0, j)),
            pl.BlockSpec((1, BLOCK_P), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_D, BLOCK_P), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, p), jnp.uint32),
        interpret=on_cpu,  # the Pallas interpreter stands in for the chip
    )(h, mask, a.reshape(1, p), b.reshape(1, p))


@jax.jit
def minhash_pallas(h: jnp.ndarray, mask: jnp.ndarray, a: jnp.ndarray,
                   b: jnp.ndarray) -> jnp.ndarray:
    """h (D, S) uint32, mask (D, S) bool, a/b (P,) uint32 -> (D, P) uint32.

    D must be a multiple of BLOCK_D and P of BLOCK_P (ops.py pads).
    """
    d, p = h.shape[0], a.shape[0]
    if d % BLOCK_D or p % BLOCK_P:
        raise ValueError(f"D={d} and P={p} must be multiples of {BLOCK_D} and {BLOCK_P}")
    return jax.lax.platform_dependent(
        h, mask, a, b,
        cpu=lambda *xs: _call(*xs, on_cpu=True),
        default=lambda *xs: _call(*xs, on_cpu=False),
    )
