"""Public wrapper: BLAKE2b-64 of many short slices of one byte buffer, in
fixed-size device tiles."""
from __future__ import annotations

import numpy as np

from repro.kernels.blake2b.blake2b import BLOCK_BYTES, blake2b64_tile

# messages per device call: every call has this one shape, so the hash
# compiles once per process whatever the number of messages
TILE = 16384


def blake2b64(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """``buf`` uint8 (1-D); message ``i`` is ``buf[starts[i]:starts[i] +
    lens[i]]``, with ``lens`` in [0, 128]. Returns (n,) uint64:
    ``hashlib.blake2b(message, digest_size=8)`` of each message read
    little-endian, bit for bit.

    Each message is copied out as the 128-byte window of ``buf`` at its start
    (the device zeroes the bytes past its length) into tiles of :data:`TILE`
    messages, the last one padded with empty messages. Every tile is
    dispatched before the first result is read back."""
    starts = np.asarray(starts, np.int64)
    lens = np.asarray(lens, np.int32)
    n = len(starts)
    if n == 0:
        return np.zeros(0, np.uint64)
    if lens.min() < 0 or lens.max() > BLOCK_BYTES:
        raise ValueError(f"message lengths must lie in [0, {BLOCK_BYTES}]")
    if starts.min() < 0 or np.max(starts + lens) > len(buf):
        raise ValueError("messages must lie inside the buffer")
    padded = np.concatenate([np.asarray(buf, np.uint8), np.zeros(BLOCK_BYTES, np.uint8)])
    windows = np.lib.stride_tricks.as_strided(
        padded, (len(buf) + 1, BLOCK_BYTES), (1, 1), writeable=False)
    outs = []
    for i in range(0, n, TILE):
        rows, tile_lens = windows[starts[i:i + TILE]], lens[i:i + TILE]
        if len(rows) < TILE:  # the last tile: pad with empty messages
            rows = np.concatenate([rows, np.zeros((TILE - len(rows), BLOCK_BYTES), np.uint8)])
            tile_lens = np.concatenate([tile_lens, np.zeros(TILE - len(tile_lens), np.int32)])
        outs.append(blake2b64_tile(rows.view("<u4").reshape(TILE // 4, 128),
                                   tile_lens.reshape(TILE // 128, 128)))
    halves = np.concatenate([np.asarray(o).reshape(2, TILE) for o in outs], axis=1)[:, :n]
    return halves[0].astype(np.uint64) | (halves[1].astype(np.uint64) << np.uint64(32))
