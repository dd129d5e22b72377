"""Plain reference of streaming MinHash-LSH dedup with keep-first semantics.

Written from the method as the recipe states it:

- shingles: word ``ngram``-grams joined by one space (a document with fewer
  words is one shingle, the empty text the empty shingle); above
  ``max_shingles`` shingles, ``max_shingles`` of them taken at positions
  ``int(i * count / max_shingles)``;
- a shingle's hash: the first 8 bytes of its BLAKE2b digest (``digest_size
  8``), little-endian, folded to 32 bits as ``low ^ high``;
- permutations: ``numpy.random.default_rng(seed)`` draws ``a`` in
  ``[1, 2**61 - 2)`` and then ``b`` in ``[0, 2**61 - 2)``, ``num_permutations``
  each as uint64; the 32-bit family uses ``a | 1`` and ``b`` cut to 32 bits;
- signature: per permutation the least ``(a * h + b) mod 2**32`` over the
  shingles;
- bands: ``num_bands`` bands of ``r`` rows; a band's key is
  ``k = k * 1099511628211 + row`` over its rows, mod ``2**64``;
- candidates: in each band the first document with a key is that bucket's
  head, and every later document with the key is paired with it;
- a candidate pair counts when the Jaccard similarity of the two documents'
  shingle-hash sets is at least ``jaccard_threshold``;
- keep-first: documents arrive in order; a document is kept when, once its
  pairs are joined, no earlier document is connected to it. Its
  ``dup_component`` is its index in the arrival order.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

M61 = (1 << 61) - 1
BAND_MULT = np.uint64(1099511628211)


def shingles(text: str, ngram: int, max_shingles: int) -> List[str]:
    words = text.split()
    if len(words) < ngram:
        return [" ".join(words)]
    count = len(words) - ngram + 1
    starts = range(count)
    if count > max_shingles:
        step = count / max_shingles
        starts = [int(i * step) for i in range(max_shingles)]
    return [" ".join(words[i:i + ngram]) for i in starts]


def shingle_hashes(text: str, ngram: int, max_shingles: int) -> np.ndarray:
    digests = b"".join(hashlib.blake2b(g.encode("utf-8"), digest_size=8).digest()
                       for g in shingles(text, ngram, max_shingles))
    return np.frombuffer(digests, "<u8").astype(np.uint64)


def permutations32(n_perm: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    a = rng.integers(1, M61 - 1, size=n_perm, dtype=np.uint64)
    b = rng.integers(0, M61 - 1, size=n_perm, dtype=np.uint64)
    return a.astype(np.uint32) | np.uint32(1), b.astype(np.uint32)


def signature(h64: np.ndarray, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    h = ((h64 & np.uint64(0xFFFFFFFF)) ^ (h64 >> np.uint64(32))).astype(np.uint32)
    return (a[:, None] * h[None, :] + b[:, None]).min(axis=1)


def band_keys(sig: np.ndarray, n_bands: int) -> List[int]:
    rows = sig.reshape(n_bands, -1).astype(np.uint64)
    key = np.zeros(n_bands, np.uint64)
    for i in range(rows.shape[1]):
        key = key * BAND_MULT + rows[:, i]
    return key.tolist()


def jaccard(a: np.ndarray, b: np.ndarray) -> float:
    sa, sb = set(a.tolist()), set(b.tolist())
    return len(sa & sb) / max(1, len(sa | sb))


class KeepFirst:
    """Incremental keep-first dedup over documents in arrival order."""

    def __init__(self, step: Dict, max_shingles: int, seed: int):
        self.ngram = step.get("ngram", 5)
        self.n_bands = step["num_bands"]
        self.threshold = step["jaccard_threshold"]
        self.max_shingles = max_shingles
        self.a, self.b = permutations32(step["num_permutations"], seed)
        self.buckets: List[Dict[int, int]] = [dict() for _ in range(self.n_bands)]
        self.hashes: List[np.ndarray] = []
        self.parent: List[int] = []
        self.n_shingles: List[int] = []

    def _root(self, x: int) -> int:
        while self.parent[x] != x:
            self.parent[x] = self.parent[self.parent[x]]
            x = self.parent[x]
        return x

    def sketch(self, text: str) -> Tuple[np.ndarray, List[int]]:
        """A document's shingle hashes and band keys, which depend on no
        other document."""
        h = shingle_hashes(text, self.ngram, self.max_shingles)
        return h, band_keys(signature(h, self.a, self.b), self.n_bands)

    def add(self, text: str) -> bool:
        """Ingest the next document; True when keep-first keeps it."""
        return self.ingest(*self.sketch(text))

    def ingest(self, h: np.ndarray, keys: List[int]) -> bool:
        """Ingest the next document by its sketch; True when keep-first
        keeps it."""
        gid = len(self.parent)
        self.hashes.append(h)
        self.n_shingles.append(len(h))
        self.parent.append(gid)
        for band, key in enumerate(keys):
            head = self.buckets[band].setdefault(key, gid)
            if head != gid and jaccard(self.hashes[head], h) >= self.threshold:
                ra, rb = self._root(head), self._root(gid)
                if ra != rb:
                    # the smaller root stays root: a root is its component's least member
                    self.parent[max(ra, rb)] = min(ra, rb)
        return self._root(gid) == gid


def keep_first(texts: Sequence[str], step: Dict, max_shingles: int, seed: int) -> List[bool]:
    kf = KeepFirst(step, max_shingles, seed)
    return [kf.add(t) for t in texts]
