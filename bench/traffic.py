"""The one corpus generator: a traffic mix is a JSON file of parameters under
``bench/traffic/<name>.json``; this module turns it and ``--seed`` into a
JSONL corpus.

Every row is ``{"text": ..., "meta": {"id": i}}``: ``id`` is the row's
position in the corpus, so the checks can line exported rows up with their
input.

What a seed changes and what it does not. The *shape* of a corpus (the
document lengths, which documents carry junk and how much, the duplicate
clusters, which source each copies and how heavily each copy is edited) is
drawn from ``shape_seed``, a constant of the mix, so every seed does the
same amount of work. ``--seed`` draws the words, the junk characters, which
words of a copy are edited, and the order. The order is stratified by
length: the documents are dealt into ``STRATA`` strata by word count, and
each run of ``STRATA`` consecutive documents holds one of each stratum, so
any stretch of the corpus that a window reads has the whole corpus's mix of
lengths. Word ids are drawn in bulk with numpy; each document is rendered
with one bytes join.

Parameters (every number is the mix's own, with its reason in the file):

- ``n_docs``: documents in the corpus;
- ``vocab_size``, ``word_len``, ``zipf_a``: a synthetic vocabulary of
  ``vocab_size`` lowercase words of ``word_len[0]..word_len[1]`` letters,
  drawn with Zipf exponent ``zipf_a`` over their ranks. The vocabulary is
  the same for every mix and seed (``VOCAB_SEED``);
- ``words``: ``{"median", "sigma", "min", "max"}`` of a lognormal word count;
- ``exact_dup_frac``, ``near_dup_frac``: shares of the corpus that are exact
  and edited copies of another document;
- ``cluster_zipf``: copies per source document are Zipf-distributed with
  this exponent (heavy-tailed clusters);
- ``near_dup_edit``: ``[lo, hi]``, the share of a near copy's words that are
  dropped or replaced, uniform per copy; half the edits drop a word, half
  replace it with a vocabulary word;
- ``noise_frac``, ``noise_len``: share of source documents with one run of
  ``noise_len[0]..noise_len[1]`` junk characters, before or after the text
  (copies inherit it).
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Dict, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
VOCAB_SEED = 20240501
# no quote or backslash: junk needs no JSON escaping
JUNK = np.frombuffer(b"!@#$%^&*<>{}[]|~`", np.uint8)
CHUNK_DOCS = 4096
STRATA = 64
TABLE = 1 << 16


def load_mix(name: str) -> Dict[str, Any]:
    """The mix ``bench/traffic/<name>.json``; a name ending in ``.json`` is
    read as a path."""
    path = Path(name) if name.endswith(".json") else TRAFFIC_DIR / f"{name}.json"
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def make_vocab(size: int, word_len: Tuple[int, int]) -> Tuple[np.ndarray, np.ndarray]:
    """``(buf, offsets)``: ``size`` words of random lowercase letters,
    concatenated; word ``k`` is ``buf[offsets[k]:offsets[k + 1]]``."""
    rng = np.random.default_rng(VOCAB_SEED)
    lens = rng.integers(word_len[0], word_len[1] + 1, size)
    offsets = np.zeros(size + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    buf = rng.integers(ord("a"), ord("z") + 1, int(offsets[-1])).astype(np.uint8)
    return buf, offsets


def _zipf_sampler(n: int, a: float, rng: np.random.Generator):
    """Draws word ids with P(rank r) proportional to r**-a; ranks are dealt
    to words by a fixed permutation."""
    cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** a)
    cdf /= cdf[-1]
    rank_to_word = np.random.default_rng(VOCAB_SEED + 1).permutation(n)
    # the rank of a draw u is the first r with cdf[r] > u; a table of that
    # rank at each of TABLE evenly spaced u starts the search close to it
    table = np.searchsorted(cdf, np.arange(TABLE) / TABLE, side="right")

    def draw(k: int) -> np.ndarray:
        u = rng.random(k)
        ranks = table[(u * TABLE).astype(np.int64)]
        todo = np.flatnonzero(cdf[ranks] <= u)
        while todo.size:
            ranks[todo] += 1
            todo = todo[(ranks[todo] < n - 1) & (cdf[ranks[todo]] <= u[todo])]
        return rank_to_word[ranks]
    return draw


def corpus_shape(mix: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """The seed-independent shape: word counts of the source documents,
    which sources carry junk, copies per source, and which copies are
    exact."""
    rng = np.random.default_rng(int(mix["shape_seed"]))
    n = int(mix["n_docs"])
    n_copies = int(round(n * (mix["exact_dup_frac"] + mix["near_dup_frac"])))
    sizes = []
    total = 0
    while total < n_copies:
        k = min(int(rng.zipf(mix["cluster_zipf"])), n_copies - total)
        sizes.append(k)
        total += k
    sizes = np.asarray(sizes, np.int64)
    n_src = n - n_copies
    if len(sizes) > n_src:
        raise ValueError("more duplicate clusters than source documents")
    w = mix["words"]
    counts = np.exp(np.log(w["median"]) + w["sigma"] * rng.standard_normal(n_src))
    counts = np.clip(np.rint(counts), w["min"], w["max"]).astype(np.int64)
    exact = np.zeros(n_copies, bool)
    n_exact = int(round(n * mix["exact_dup_frac"]))
    exact[rng.permutation(n_copies)[:n_exact]] = True
    noisy = np.zeros(n_src, bool)
    noisy[rng.permutation(n_src)[:int(round(n_src * mix["noise_frac"]))]] = True
    jlo, jhi = mix["noise_len"]
    lo, hi = mix["near_dup_edit"]
    return {"counts": counts, "cluster_sizes": sizes, "exact": exact, "noisy": noisy,
            "junk_len": rng.integers(jlo, jhi + 1, int(noisy.sum())),
            "junk_before": rng.random(int(noisy.sum())) < 0.5,
            # each cluster copies one distinct source document
            "copy_of": np.repeat(rng.choice(n_src, size=len(sizes), replace=False), sizes),
            "edit_rate": rng.uniform(lo, hi, n_copies)}


def stratified_order(lengths: np.ndarray, rng: np.random.Generator,
                     strata: int = STRATA) -> np.ndarray:
    """A random order of ``len(lengths)`` documents in which each run of
    ``strata`` consecutive positions holds one document of each length
    stratum (the last run may hold fewer)."""
    n = len(lengths)
    per = -(-n // strata)
    grid = np.full(strata * per, -1, np.int64)
    grid[:n] = np.argsort(lengths, kind="stable")
    grid = rng.permuted(grid.reshape(strata, per), axis=1)  # shuffle within a stratum
    rounds = rng.permuted(grid.T, axis=1)  # shuffle within each run
    order = rounds.ravel()
    return order[order >= 0]


def generate(mix: Dict[str, Any], seed: int, path: str) -> Dict[str, Any]:
    """Write the corpus to ``path``; returns its size and the count of each
    kind of document."""
    shape = corpus_shape(mix)
    rng = np.random.default_rng(seed)
    vbuf, voff = make_vocab(int(mix["vocab_size"]), tuple(mix["word_len"]))
    vocab = [vbuf[a:b].tobytes() for a, b in zip(voff[:-1].tolist(), voff[1:].tolist())]
    draw = _zipf_sampler(len(vocab), float(mix["zipf_a"]), rng)

    counts = shape["counts"]
    n_src = len(counts)
    src = np.split(draw(int(counts.sum())), np.cumsum(counts)[:-1])
    docs = [b" ".join(map(vocab.__getitem__, w.tolist())) for w in src]
    # junk runs go on source documents, so exact copies of them stay exact
    noisy = np.flatnonzero(shape["noisy"]).tolist()
    for d, k, first in zip(noisy, shape["junk_len"].tolist(), shape["junk_before"].tolist()):
        junk = JUNK[rng.integers(0, len(JUNK), k)].tobytes()
        docs[d] = junk + b" " + docs[d] if first else docs[d] + b" " + junk
    copy_of = shape["copy_of"]
    for s, exact, rate in zip(copy_of.tolist(), shape["exact"].tolist(),
                              shape["edit_rate"].tolist()):
        if exact:
            docs.append(docs[s])
            continue
        words = docs[s].split(b" ")
        edit = rng.random(len(words)) < rate
        drop = edit & (rng.random(len(words)) < 0.5)
        repl = np.flatnonzero(edit & ~drop).tolist()
        for i, w in zip(repl, draw(len(repl)).tolist()):
            words[i] = vocab[w]
        kept = [w for w, x in zip(words, drop.tolist()) if not x]
        docs.append(b" ".join(kept or words))
    order = stratified_order(np.concatenate([counts, counts[copy_of]]), rng).tolist()
    n_bytes = 0
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        for c0 in range(0, len(order), CHUNK_DOCS):
            blob = b"".join(
                b'{"text":"' + docs[d] + b'","meta":{"id":' + str(c0 + k).encode() + b"}}\n"
                for k, d in enumerate(order[c0:c0 + CHUNK_DOCS]))
            n_bytes += len(blob)
            f.write(blob)
    os.replace(tmp, path)
    return {"docs": len(docs), "bytes": n_bytes, "sources": n_src,
            "exact_copies": int(shape["exact"].sum()),
            "near_copies": int((~shape["exact"]).sum()), "noisy_sources": len(noisy),
            "max_words": int(counts.max()) + int(bool(noisy))}
