"""The corpus generator: deterministic per seed, with the stated shape."""
import json

import numpy as np
import pytest

from bench import traffic


def _gen(tmp_path, name, n, seed):
    mix = traffic.load_mix(name)
    mix["n_docs"] = n
    path = tmp_path / f"{name}_{seed}.jsonl"
    info = traffic.generate(mix, seed, str(path))
    return mix, info, path.read_bytes()


@pytest.mark.parametrize("name", ["web", "short", "near_dup"])
def test_same_seed_same_bytes_other_seed_same_shape(tmp_path, name):
    _, info, a = _gen(tmp_path, name, 3000, 2**31 + 5)
    _, _, b = _gen(tmp_path, name, 3000, 2**31 + 5)
    _, info_c, c = _gen(tmp_path, name, 3000, 7)
    assert a == b and a != c
    assert info == dict(info_c, bytes=info["bytes"])
    rows = [json.loads(x) for x in a.splitlines()]
    assert [r["meta"]["id"] for r in rows] == list(range(3000))
    assert all(set(r) == {"text", "meta"} for r in rows)


@pytest.mark.parametrize("name", ["web", "near_dup"])
def test_every_seed_and_every_stretch_does_the_same_work(tmp_path, name):
    """Seeds change words and order, not sizes: corpora of two seeds hold the
    same word counts, and any stretch a window reads has the corpus's mix."""
    counts = []
    for seed in (2**31 + 17, 99):
        _, info, raw = _gen(tmp_path, name, 4000, seed)
        words = np.array([len(json.loads(x)["text"].split()) for x in raw.splitlines()])
        assert abs(words.sum() / counts[0].sum() - 1) < 0.01 if counts else True
        counts.append(words)
        means = [w.mean() for w in np.array_split(words, 4)]
        assert max(means) / min(means) < 1.05, means
    assert not np.array_equal(counts[0], counts[1])  # another order


def test_web_lengths_and_duplicates(tmp_path):
    mix, info, raw = _gen(tmp_path, "web", 6000, 11)
    texts = [json.loads(x)["text"] for x in raw.splitlines()]
    words = np.array([len(t.split()) for t in texts])
    assert 225 <= np.median(words) <= 280  # lognormal median 250 (plus a junk run on 15%)
    assert words.min() >= 18 and words.max() <= mix["words"]["max"] + 1
    assert np.mean(words > 1000) > 0.05  # the heavy tail: P(z > ln 4) = 8% for sigma 1
    n = len(texts)
    assert info["exact_copies"] == round(n * 0.05) and info["near_copies"] == round(n * 0.08)
    # every exact copy repeats some other row's text
    assert n - len(set(texts)) >= info["exact_copies"]
    junk = sum(any(c in t for c in "!@#$%^&*") for t in texts)
    assert 0.12 * n <= junk <= 0.2 * n


def test_short_lengths(tmp_path):
    mix, info, raw = _gen(tmp_path, "short", 6000, 11)
    words = np.array([len(json.loads(x)["text"].split()) for x in raw.splitlines()])
    assert 18 <= np.median(words) <= 23
    assert words.min() >= 3 and words.max() <= 129
    assert info["near_copies"] == 0 and info["exact_copies"] == 600


def test_near_dup_clusters_are_heavy_tailed(tmp_path):
    mix = traffic.load_mix("near_dup")
    shape = traffic.corpus_shape(mix)
    sizes = shape["cluster_sizes"]
    assert sizes.sum() == round(mix["n_docs"] * 0.45)
    assert np.mean(sizes == 1) > 0.5 and sizes.max() >= 20
