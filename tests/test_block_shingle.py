"""The block shingler and its device hash, against ``hashlib`` and the
per-document ``shingle_hashes`` they must reproduce bit for bit."""
import hashlib

import numpy as np
import pytest

from repro.core import obs
from repro.core.columnar import ColumnBlock
from repro.core.dedup.block_shingle import NON_ASCII_WHITESPACE, block_shingle_hashes
from repro.core.dedup.minhash import shingle_hashes
from repro.core.dedup.streaming import SignatureBatcher, StreamingMinHashState
from repro.core.storage import SampleBlock
from repro.kernels.blake2b.blake2b import blake2b64_tile
from repro.kernels.blake2b.ops import TILE, blake2b64


def _hashlib64(msg: bytes) -> np.uint64:
    return np.frombuffer(hashlib.blake2b(msg, digest_size=8).digest(), np.uint64)[0]


def _buffer(messages, filler=0):
    """The messages laid end to end, ``filler`` bytes between them, so that
    the bytes after each message are not zero."""
    gap = bytes([filler]) * 3
    buf = gap.join(messages) + gap
    starts = np.cumsum([0] + [len(m) + 3 for m in messages[:-1]])
    return np.frombuffer(buf, np.uint8), starts, np.array([len(m) for m in messages])


def _random_messages(n, seed):
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 129, n)
    lens[:4] = [0, 1, 127, 128]
    return [rng.integers(0, 256, k).astype(np.uint8).tobytes() for k in lens]


@pytest.mark.parametrize("n", [4, 300, TILE, TILE + 5])
def test_blake2b64_matches_hashlib_on_random_bytes(n):
    """Lengths 0..128, one tile, a whole tile, and a padded second tile."""
    msgs = _random_messages(n, seed=n)
    got = blake2b64(*_buffer(msgs, filler=0xAB))
    assert got.dtype == np.uint64 and got.shape == (n,)
    assert np.array_equal(got, [_hashlib64(m) for m in msgs])


def test_blake2b64_matches_hashlib_on_utf8_text():
    texts = ["héllo wörld", "漢字 テスト 한국어", "emoji 😀 and ünïcode", "Ω" * 64, "a" * 128, ""]
    msgs = [t.encode("utf-8") for t in texts]
    assert all(len(m) <= 128 for m in msgs)
    assert np.array_equal(blake2b64(*_buffer(msgs)), [_hashlib64(m) for m in msgs])


@pytest.mark.parametrize("start,length", [(0, 129), (200, 10), (-1, 4)])
def test_blake2b64_refuses_messages_it_cannot_hash(start, length):
    """Longer than one block, or outside the buffer."""
    with pytest.raises(ValueError):
        blake2b64(np.zeros(205, np.uint8), np.array([start]), np.array([length]))


def _words(n, width=5):
    return " ".join(f"w{i:0{width}d}" for i in range(n))


ASCII_WS = "".join(c for c in map(chr, range(128)) if c.isspace())
CASES = {
    "every_ascii_space": "a" + "b".join(ASCII_WS) + "c d e f g h",
    "runs_and_edges": "  \t\n lead  a\t\tb \x1c\x1d c\x1e\x1fd  e f  \r\n trail \x0b\x0c ",
    "empty": "",
    "spaces_only": " \t \n",
    "one_word": "alone",
    "four_words": "a bb ccc dddd",
    "five_words": "a bb ccc dddd eeeee",
    "six_words": "a bb ccc dddd eeeee ffffff",
    "grams_512": _words(516),
    "grams_513": _words(517),
    "grams_1000": _words(1004),
    "grams_16380": _words(16384),
    "junk_over_128": "one two " + "x" * 90 + " three four five six seven " + "y" * 130 + " end",
    "nbsp": "left\xa0right and some more words here",
    "ideographic_space": "左　右 and some more words here",
    "next_line": "one\x85two three four five six",
    "non_ascii_no_space": "héllo wörld ünïcode 漢字 テスト 😀 encore une fois",
    "non_ascii_spaced_oddly": "héllo\twörld  ünïcode\n漢字 テスト 😀 encore une fois",
}


def _utf8(texts):
    enc = [t.encode("utf-8") for t in texts]
    offsets = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(e) for e in enc], out=offsets[1:])
    return offsets, b"".join(enc)


@pytest.mark.parametrize("form", ["texts", "utf8"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_block_shingler_matches_shingle_hashes(name, form):
    """Each case alone and inside a block of neighbours, so that words never
    leak across document bounds."""
    texts = ["first doc", CASES[name], "x", CASES[name] + " tail", ""]
    got = (block_shingle_hashes(texts) if form == "texts"
           else block_shingle_hashes(None, utf8=_utf8(texts)))
    assert len(got) == len(texts)
    for t, g in zip(texts, got):
        want = shingle_hashes(t)
        assert g.dtype == want.dtype and np.array_equal(g, want)


@pytest.mark.parametrize("n,max_shingles", [(3, 512), (5, 7), (1, 512)])
def test_block_shingler_other_gram_sizes(n, max_shingles):
    texts = list(CASES.values())
    got = block_shingle_hashes(texts, n=n, max_shingles=max_shingles)
    for t, g in zip(texts, got):
        assert np.array_equal(g, shingle_hashes(t, n=n, max_shingles=max_shingles))


def _counts():
    c = obs.metrics().snapshot()["counters"]
    return (c.get("dedup.shingle_device_grams_total", 0),
            c.get("dedup.shingle_host_grams_total", 0))


@pytest.mark.parametrize("texts,host", [
    ([_words(40, 3), "a b c", "", "short one", _words(600, 2)], 0),
    (list(CASES.values()), None),
])
def test_device_and_host_grams_add_up(texts, host):
    dev0, host0 = _counts()
    got = block_shingle_hashes(texts)
    dev1, host1 = _counts()
    assert (dev1 - dev0) + (host1 - host0) == sum(g.size for g in got)
    if host is not None:
        assert host1 - host0 == host
    else:  # the junk grams and the three rows with non-ASCII whitespace
        assert host1 - host0 > 0 and dev1 - dev0 > 0


def test_hash_compiles_at_most_twice_over_block_sizes():
    for n_docs in (1, 7, 40, 90):  # 90 documents of 300 grams pass one tile
        block_shingle_hashes([_words(304, 4)] * n_docs)
    assert blake2b64_tile._cache_size() <= 2


def test_non_ascii_whitespace_set_is_complete():
    assert set(NON_ASCII_WHITESPACE) == {
        c for c in map(chr, range(0x80, 0x110000)) if c.isspace()}


def test_batcher_add_block_equals_add():
    texts = list(CASES.values())
    a = SignatureBatcher(n_perm=128, use_kernel=True)
    b = SignatureBatcher(n_perm=128, use_kernel=True)
    a.add_block(texts, list(range(len(texts))))
    for i, t in enumerate(texts):
        b.add(t, i)
    assert a.docs_in == b.docs_in == len(texts)
    assert a._payloads == b._payloads
    assert all(np.array_equal(x, y) for x, y in zip(a._docs, b._docs))


def _dedup(blocks, monkeypatch, per_doc, exact):
    if per_doc:
        def add_block(self, texts, payloads, utf8=None):
            for t, p in zip(texts, payloads):
                self.add(t, p)
        monkeypatch.setattr(SignatureBatcher, "add_block", add_block)
    state = StreamingMinHashState(n_perm=128, n_bands=16, use_kernel=True, super_batch=64,
                                  exact=exact)
    kept = []
    for out, _ in state.stream_blocks(blocks):
        kept.extend(out.samples)
    monkeypatch.undo()
    return [(s["id"], s["text"], s["stats"]["dup_component"]) for s in kept]


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("columnar", [False, True])
def test_keep_first_stream_same_through_block_and_per_doc_shingling(monkeypatch, columnar,
                                                                    exact):
    """Keep-first (and the exact mode's two passes) over a small corpus of
    copies and near copies: the same rows and components either way."""
    from repro.data.synthetic import make_corpus

    rows = [{"id": i, "text": s["text"]} for i, s in enumerate(make_corpus(240, seed=7))]

    def blocks():
        for i in range(0, len(rows), 50):
            part = [dict(r) for r in rows[i:i + 50]]
            yield ColumnBlock.from_samples(part) if columnar else SampleBlock(part)

    new = _dedup(blocks(), monkeypatch, per_doc=False, exact=exact)
    old = _dedup(blocks(), monkeypatch, per_doc=True, exact=exact)
    assert new == old
    assert len(new) < len(rows)  # the corpus's copies were found
