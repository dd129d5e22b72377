"""Public wrapper: uint32 folding + padding + dispatch to the Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.minhash.minhash import BLOCK_D, BLOCK_P, minhash_pallas

# smallest shingle-axis bucket; buckets grow by powers of two (64, 128, ...)
_MIN_BUCKET_S = 64


def _bucket_up(n: int, base: int) -> int:
    """Next power-of-two multiple of ``base`` that is >= n."""
    m = base
    while m < n:
        m *= 2
    return m


def minhash_signatures(
    hashes: np.ndarray, mask: np.ndarray, a: np.ndarray, b: np.ndarray,
) -> jnp.ndarray:
    """hashes (D, S) uint64/uint32, mask (D, S) bool, a/b (P,) any int ->
    (D, P) uint32 signatures. Inputs are folded to uint32 and padded to
    kernel block multiples.

    D and S pad up to power-of-two buckets instead of exact block
    multiples: the S axis is a compile-time shape (the kernel statically
    unrolls its chunk loop), so the streaming ``SignatureBatcher`` — which
    dispatches super-batch after super-batch with varying doc counts and
    shingle widths — would otherwise compile a fresh kernel per distinct
    shape. Bucketing bounds the compile cache to O(log) shapes; padded
    shingles carry ``mask=False`` (min-ignored), padded doc rows and
    permutations are sliced off, so values never change.
    """
    h32 = (np.asarray(hashes, np.uint64) & 0xFFFFFFFF).astype(np.uint32) ^ (
        np.asarray(hashes, np.uint64) >> np.uint64(32)
    ).astype(np.uint32)
    a32 = (np.asarray(a, np.uint64).astype(np.uint32) | np.uint32(1))  # odd multipliers
    b32 = np.asarray(b, np.uint64).astype(np.uint32)
    d, s = h32.shape
    p = a32.shape[0]
    pd = _bucket_up(d, BLOCK_D) - d
    ps = _bucket_up(s, _MIN_BUCKET_S) - s
    pp = (-p) % BLOCK_P
    if pd or ps:
        h32 = np.pad(h32, ((0, pd), (0, ps)))
        mask = np.pad(mask, ((0, pd), (0, ps)))
    if pp:
        a32 = np.pad(a32, (0, pp), constant_values=1)
        b32 = np.pad(b32, (0, pp))
    out = minhash_pallas(
        jnp.asarray(h32), jnp.asarray(mask), jnp.asarray(a32), jnp.asarray(b32))
    return out[:d, :p]


def minhash_signatures_packed(
    values: np.ndarray, offsets: np.ndarray, a: np.ndarray, b: np.ndarray,
) -> jnp.ndarray:
    """Packed-ragged entry point: ``values`` is the concatenation of every
    doc's shingle hashes in doc order, ``offsets`` (n_docs + 1,) delimits
    docs — the same offsets-plus-buffer layout ``repro.core.columnar`` uses
    for string columns. The dense (D, S_max) matrix + mask are built with a
    single vectorized scatter instead of a per-doc Python loop, then
    dispatched through :func:`minhash_signatures` — identical values."""
    offsets = np.asarray(offsets, np.int64)
    values = np.asarray(values, np.uint64)
    n = offsets.size - 1
    if n <= 0:
        return minhash_signatures(np.zeros((0, 1), np.uint64),
                                  np.zeros((0, 1), bool), a, b)
    if offsets[0] != 0 or offsets[-1] != values.size or np.any(np.diff(offsets) < 0):
        raise ValueError("offsets must be monotonic, start at 0 and span values")
    lens = np.diff(offsets)
    s_max = max(int(lens.max()), 1)
    mask = np.arange(s_max, dtype=np.int64)[None, :] < lens[:, None]
    padded = np.zeros((n, s_max), dtype=np.uint64)
    # row-major True positions of mask enumerate docs in order == values order
    padded[mask] = values
    return minhash_signatures(padded, mask, a, b)
