"""Shingle a whole block of documents at once, hashing on the device.

:func:`block_shingle_hashes` returns, for every document of a block, the same
uint64 array as :func:`repro.core.dedup.minhash.shingle_hashes`, bit for bit:
the kept word n-grams, each joined by single spaces and hashed with
BLAKE2b-64. The host lays the block out with vectorized byte work (word
boundaries from a byte table, the kept n-grams as windows of one buffer) and
``repro.kernels.blake2b`` hashes every n-gram of the block in fixed-size
device tiles.

Two kinds of n-gram are hashed by ``hashlib`` on the host instead, and
counted apart: n-grams longer than one BLAKE2b block (128 bytes), and every
n-gram of a document that holds a non-ASCII whitespace character, which the
ASCII byte table cannot see (such a document takes ``shingle_hashes``
whole). ASCII whitespace bytes never occur inside a multi-byte UTF-8
sequence, so the table is exact for every other document.
"""
from __future__ import annotations

import hashlib
import re
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core import obs
from repro.core.columnar import _WS_BYTE, ascii_rows_mask
from repro.core.dedup.minhash import shingle_hashes

# every character above U+007F for which str.isspace() holds: str.split()
# breaks words there, the ASCII byte table does not
NON_ASCII_WHITESPACE = (
    "\x85\xa0\u1680\u2000\u2001\u2002\u2003\u2004\u2005\u2006\u2007\u2008"
    "\u2009\u200a\u2028\u2029\u202f\u205f\u3000")
_NON_ASCII_WS = re.compile(f"[{NON_ASCII_WHITESPACE}]")
_SPACE = 0x20


def block_shingle_hashes(texts: Optional[Sequence[str]], n: int = 5,
                         max_shingles: int = 512,
                         utf8: Optional[Tuple[np.ndarray, bytes]] = None,
                         ) -> List[np.ndarray]:
    """Per document, ``shingle_hashes(text, n, max_shingles)``. The block is
    ``texts``, or ``utf8``: a string column's ``(offsets, buffer)``, which
    saves encoding it again (``texts`` may then be None)."""
    from repro.kernels.blake2b.ops import BLOCK_BYTES, blake2b64

    if utf8 is None:
        enc = [t.encode("utf-8") for t in texts]
        offsets = np.zeros(len(enc) + 1, np.int64)
        np.cumsum([len(e) for e in enc], out=offsets[1:])
        buf = b"".join(enc)
    else:
        offsets, buf = np.asarray(utf8[0], np.int64), utf8[1]
    n_docs = len(offsets) - 1
    if n_docs == 0:
        return []

    def text(d: int) -> str:
        return texts[d] if texts is not None else \
            buf[offsets[d]:offsets[d + 1]].decode("utf-8")

    arr = np.frombuffer(buf, np.uint8)
    host_docs = []
    if len(arr) and arr.max() >= 0x80:
        host_docs = [d for d in np.flatnonzero(~ascii_rows_mask(offsets, buf)).tolist()
                     if _NON_ASCII_WS.search(text(d))]
    starts, ends = _words(arr, offsets)
    # words of document d: starts[wbase[d]:wbase[d] + nw[d]]
    wbase = np.searchsorted(starts, offsets[:-1])
    nw = np.searchsorted(starts, offsets[1:]) - wbase
    arr, starts, ends = _single_spaced(arr, starts, ends, wbase + nw - 1)

    # kept n-grams per document, with the float64 arithmetic of shingle_hashes
    grams = np.where(nw < n, 1, nw - n + 1)
    kept = np.minimum(grams, max_shingles)
    kept[host_docs] = 0
    first = np.zeros(n_docs + 1, np.int64)
    np.cumsum(kept, out=first[1:])
    doc = np.repeat(np.arange(n_docs), kept)
    i = np.arange(first[-1]) - first[doc]
    sampled = grams[doc] > max_shingles
    k = np.where(sampled, (i * (grams[doc] / max_shingles)).astype(np.int64), i)
    w0 = wbase[doc] + k
    width = np.minimum(nw[doc], n)
    lo = starts[np.minimum(w0, len(starts) - 1)] if len(starts) else np.zeros_like(w0)
    hi = ends[np.maximum(w0 + width - 1, 0)] if len(ends) else np.zeros_like(w0)
    lens = np.where(width > 0, hi - lo, 0)
    lo = np.where(width > 0, lo, 0)

    out = np.empty(first[-1], np.uint64)
    on_device = lens <= BLOCK_BYTES
    dev = np.flatnonzero(on_device)
    out[dev] = blake2b64(arr, lo[dev], lens[dev])
    long_grams = np.flatnonzero(~on_device).tolist()
    raw = arr.tobytes() if long_grams else b""
    for g in long_grams:
        s = int(lo[g])
        out[g] = np.frombuffer(hashlib.blake2b(
            raw[s:s + int(lens[g])], digest_size=8).digest(), np.uint64)[0]

    docs = np.split(out, first[1:-1])
    host_grams = len(long_grams)
    for d in host_docs:
        docs[d] = shingle_hashes(text(d), n=n, max_shingles=max_shingles)
        host_grams += docs[d].size
    m = obs.metrics()
    m.inc("dedup.shingle_device_grams_total", len(dev))
    m.inc("dedup.shingle_host_grams_total", host_grams)
    return docs


def _words(arr: np.ndarray, offsets: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Start and end (exclusive) byte of every whitespace-delimited word of
    the block, in order; no word crosses a document's bounds."""
    inword = np.zeros(len(arr) + 2, np.int8)
    inword[1:-1] = ~np.take(_WS_BYTE, arr)
    edge = np.diff(inword)  # +1 where a word starts, -1 where one has ended
    starts, ends = np.flatnonzero(edge == 1), np.flatnonzero(edge == -1)
    # a document bound inside a run of word bytes splits the run
    cut = offsets[(offsets > 0) & (offsets < len(arr))]
    cut = np.unique(cut[(inword[cut] > 0) & (inword[cut + 1] > 0)])
    return (np.insert(starts, np.searchsorted(starts, cut), cut),
            np.insert(ends, np.searchsorted(ends, cut), cut))


def _single_spaced(arr: np.ndarray, starts: np.ndarray, ends: np.ndarray,
                   last: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The block with every word followed by exactly one space, so that an
    n-gram ``" ".join(words)`` is one window of it; the block itself where
    that already holds between the words of each document. ``last`` holds
    the index of each document's last word (-1 or a repeat where it has
    none)."""
    inner = np.ones(max(len(starts) - 1, 0), bool)
    inner[last[(last >= 0) & (last < len(inner))]] = False
    between = ends[:-1][inner]
    if np.all(starts[1:][inner] - between == 1) and np.all(arr[between] == _SPACE):
        return arr, starts, ends
    lens = ends - starts
    new_starts = np.zeros(len(starts), np.int64)
    np.cumsum(lens[:-1] + 1, out=new_starts[1:])
    new_ends = new_starts + lens
    spaced = np.full(int(new_ends[-1]) + 1, _SPACE, np.uint8)
    dest = np.ones(len(spaced), bool)
    dest[new_ends] = False
    dest = np.flatnonzero(dest)
    spaced[dest] = arr[dest + np.repeat(starts - new_starts, lens)]
    return spaced, new_starts, new_ends
