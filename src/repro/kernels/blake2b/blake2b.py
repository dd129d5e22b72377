"""BLAKE2b-64 of short messages, batched: one compression per message.

Dedup's shingle hash is ``hashlib.blake2b(gram, digest_size=8)`` read
little-endian as uint64 (``core/dedup/minhash.shingle_hashes``). A message of
at most 128 bytes is a single BLAKE2b block, so its digest is one call of the
compression function F with the byte counter set to the message length and
the final flag set (RFC 7693 §3.2-3.3). That is fixed-width integer work over
thousands of independent messages, which this module runs as plain ``jnp``
under ``jit``.

The 64-bit words are carried as ``(lo, hi)`` pairs of uint32 arrays, so no
64-bit integer type is needed (``jax_enable_x64`` stays off): adds carry from
``lo`` to ``hi``, rotations exchange bits between the halves. Each pair's
arrays have the batch's shape, ``(rows, 128)``, which lays a tile of messages
over whole vector registers on a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_BYTES = 128

_IV = (
    0x6A09E667F3BCC908, 0xBB67AE8584CAA73B, 0x3C6EF372FE94F82B, 0xA54FF53A5F1D36F1,
    0x510E527FADE682D1, 0x9B05688C2B3E6C1F, 0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179,
)
_SIGMA = (
    (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15),
    (14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3),
    (11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4),
    (7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8),
    (9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13),
    (2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9),
    (12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11),
    (13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10),
    (6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5),
    (10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0),
)
_ROUNDS = 12
# parameter block of an unkeyed 8-byte digest, fanout 1, depth 1, folded
# into h[0]: 0x0101kknn with key length kk = 0 and digest length nn = 8
_PARAM0 = 0x01010008
# message words each round's mixes take, as four rows of four lanes: the x
# and y words of the column mixes, then those of the diagonal mixes
_SCHEDULE = tuple(
    tuple(tuple(_SIGMA[r % 10][2 * i + k] for i in range(q, q + 4))
          for q in (0, 4) for k in (0, 1))
    for r in range(_ROUNDS))


def _add(x, y):
    lo = x[0] + y[0]
    return lo, x[1] + y[1] + (lo < x[0]).astype(jnp.uint32)


def _xor(x, y):
    return x[0] ^ y[0], x[1] ^ y[1]


def _rotr(x, n: int):
    lo, hi = x
    if n >= 32:
        lo, hi, n = hi, lo, n - 32
    if n == 0:
        return lo, hi
    return (lo >> n) | (hi << (32 - n)), (hi >> n) | (lo << (32 - n))


def _roll(x, k: int):
    return jnp.roll(x[0], k, axis=0), jnp.roll(x[1], k, axis=0)


def _mix(a, b, c, d, x, y):
    """The G function on four lanes at once (RFC 7693 §3.1)."""
    a = _add(_add(a, b), x)
    d = _rotr(_xor(d, a), 32)
    c = _add(c, d)
    b = _rotr(_xor(b, c), 24)
    a = _add(_add(a, b), y)
    d = _rotr(_xor(d, a), 16)
    c = _add(c, d)
    b = _rotr(_xor(b, c), 63)
    return a, b, c, d


def _rows(values, like):
    """Four 64-bit constants as (lo, hi) arrays of shape (4,) + like.shape."""
    lo = jnp.array([v & 0xFFFFFFFF for v in values], jnp.uint32)
    hi = jnp.array([v >> 32 for v in values], jnp.uint32)
    shape = (4,) + like.shape
    pad = (slice(None),) + (None,) * like.ndim
    return jnp.broadcast_to(lo[pad], shape), jnp.broadcast_to(hi[pad], shape)


def compress_final(words: jnp.ndarray, lens: jnp.ndarray) -> tuple:
    """``words`` (32, ...) uint32: the 16 little-endian message words of each
    zero-padded 128-byte message as (lo, hi) halves in word order, ``lens``
    (...) uint32 message lengths of at most 128 bytes. Returns the ``(lo,
    hi)`` halves of the first output word, which is the 8-byte digest read
    little-endian.

    The state's 16 words are four rows of four lanes (a, b, c, d), so each
    round is two four-lane mixes: one over the columns, one over the
    diagonals after rotating rows b, c and d. The rounds run in a
    ``fori_loop`` whose body is one round."""
    sched = jnp.array(_SCHEDULE, jnp.int32)  # (rounds, 4, 4)
    m_lo, m_hi = words[0::2][sched], words[1::2][sched]  # (rounds, 4, 4, ...)
    h = (_IV[0] ^ _PARAM0,) + _IV[1:4]
    a, b = _rows(h, lens), _rows(_IV[4:], lens)
    c, d = _rows(_IV[:4], lens), _rows(_IV[4:], lens)
    ones = jnp.full_like(lens, 0xFFFFFFFF)
    # byte counter t = len (< 2**32) into v[12], final flag f0 = 2**64 - 1
    # into v[14]
    d = (d[0] ^ jnp.stack([lens, 0 * lens, ones, 0 * lens]),
         d[1] ^ jnp.stack([0 * lens, 0 * lens, ones, 0 * lens]))

    def round_(r, state):
        a, b, c, d = (state[0:2], state[2:4], state[4:6], state[6:8])
        ml, mh = m_lo[r], m_hi[r]
        a, b, c, d = _mix(a, b, c, d, (ml[0], mh[0]), (ml[1], mh[1]))
        b, c, d = _roll(b, -1), _roll(c, -2), _roll(d, -3)
        a, b, c, d = _mix(a, b, c, d, (ml[2], mh[2]), (ml[3], mh[3]))
        b, c, d = _roll(b, 1), _roll(c, 2), _roll(d, 3)
        return a + b + c + d

    v = jax.lax.fori_loop(0, _ROUNDS, round_, a + b + c + d)
    h0 = (jnp.uint32((_IV[0] ^ _PARAM0) & 0xFFFFFFFF), jnp.uint32(_IV[0] >> 32))
    return _xor(_xor(h0, (v[0][0], v[1][0])), (v[4][0], v[5][0]))


@jax.jit
def blake2b64_tile(words: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """One tile of T messages, T a multiple of 128. ``words`` (T // 4, 128)
    uint32: the (T, 128) message bytes read as little-endian uint32 and laid
    four messages to a row, so that the transfer is lane-dense; bytes from
    ``lens[t]`` on may hold anything (they are zeroed here). ``lens``
    (T // 128, 128) int32 in [0, 128]. Returns (2, T // 128, 128) uint32: the
    digests' lo and hi halves."""
    grid = words.reshape(-1, 32).T.reshape((32,) + lens.shape)
    # keep the first lens bytes of each message: word j holds bytes 4j..4j+3
    keep = jnp.clip(lens[None] - 4 * jnp.arange(32, dtype=jnp.int32)[:, None, None], 0, 4)
    mask = jnp.where(keep >= 4, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << (8 * keep).astype(jnp.uint32)) - 1)
    lo, hi = compress_final(grid & mask, lens.astype(jnp.uint32))
    return jnp.stack([lo, hi])
