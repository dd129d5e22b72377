"""The whole run with the timed path broken underneath, or a control in the
program's place: ``correct`` comes out false, under the cell's own limits,
for each fault a cell can have (an answer altered where it is produced; half
of a batch left out; a step that returns its state unchanged) and for each
control. The device check is skipped; sizes are tiny.

The scorer's op labels the last scored position of a row with the pad id
rather than the token that follows it. The scorer's cases run it with
next-token targets (``bench.calibrate.repaired_labels``), so that what turns
``correct`` false is the fault planted here."""
import json

import numpy as np
import pytest

from bench.calibrate import half_bands, repaired_labels
from bench.tests.tiny import run_tiny


def _fails(res, number):
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


@pytest.fixture
def labels():
    with repaired_labels():
        yield


def test_scorer_answer_altered(tmp_path, monkeypatch, labels):
    from repro.ops.model_ops import LMPerplexityFilter

    orig = LMPerplexityFilter._ppl_batch

    def altered(self, texts):
        out = orig(self, texts)
        out[0] *= 1.01  # 0.01 nats on one row of each batch
        return out
    monkeypatch.setattr(LMPerplexityFilter, "_ppl_batch", altered)
    _fails(run_tiny(tmp_path, "score_mamba2", n_docs=400), "scorer_gap_nats")


def test_scorer_half_batch_left_out(tmp_path, monkeypatch, labels):
    from repro.ops.model_ops import LMPerplexityFilter

    orig = LMPerplexityFilter._ppl_batch

    def half(self, texts):
        k = max(1, len(texts) // 2)
        out = orig(self, texts[:k])
        return np.concatenate([out, np.full(len(texts) - k, np.exp(np.mean(np.log(out))))])
    monkeypatch.setattr(LMPerplexityFilter, "_ppl_batch", half)
    _fails(run_tiny(tmp_path, "score_mamba2", n_docs=400), "scorer_gap_nats")


def test_scorer_control_is_not_correct(tmp_path, monkeypatch, labels):
    """The float8 reference in the scorer's place."""
    from bench.reference import mamba2
    from bench.tests.tiny import tiny_config
    from repro.ops.model_ops import LMPerplexityFilter

    sc = tiny_config("score_mamba2")["scorer"]

    def control(self, texts):
        return np.exp(mamba2.row_losses(sc, texts, sc["seq_len"], quantize=sc["control_dtype"]))
    monkeypatch.setattr(LMPerplexityFilter, "_ppl_batch", control)
    _fails(run_tiny(tmp_path, "score_mamba2", n_docs=400), "scorer_gap_nats")


def test_dedup_keeps_duplicates(tmp_path, monkeypatch):
    from repro.core.dedup.streaming import StreamingUnionFind

    monkeypatch.setattr(StreamingUnionFind, "component_min", lambda self, x: x)
    _fails(run_tiny(tmp_path, "refine_dedup", n_docs=500), "dedup_rows_wrong")


@pytest.mark.parametrize("skip", ["every_call", "every_second_call"])
def test_dedup_index_left_unchanged(tmp_path, monkeypatch, skip):
    """The LSH index returns its state unchanged on every insert, or on every
    second one (half of each super-batch left out of it)."""
    from repro.core.dedup.streaming import LSHBandIndex

    orig = LSHBandIndex.insert
    calls = {"n": 0}

    def insert(self, doc_id, band_keys, doc_hashes):
        calls["n"] += 1
        if skip == "every_call" or calls["n"] % 2 == 0:
            return []
        return orig(self, doc_id, band_keys, doc_hashes)
    monkeypatch.setattr(LSHBandIndex, "insert", insert)
    _fails(run_tiny(tmp_path, "refine_dedup", n_docs=500), "dedup_rows_wrong")


def test_dedup_control_is_not_correct(tmp_path):
    """The stream run with half the configured bands."""
    _fails(run_tiny(tmp_path, "refine_dedup", n_docs=900, program_cfg=half_bands),
           "dedup_rows_wrong")


def test_filter_stat_altered(tmp_path, monkeypatch):
    from repro.ops.text_filters import WordsNumFilter

    monkeypatch.setattr(WordsNumFilter, "_stat", lambda self, s: float(len(s["text"].split()) + 1))
    monkeypatch.setattr(WordsNumFilter, "supports_columns", lambda self: False)
    _fails(run_tiny(tmp_path, "refine_dedup", n_docs=500), "filter_rows_wrong")


def test_export_bytes_altered(tmp_path, monkeypatch):
    from repro.core import storage

    def spaced(self, block):
        for s in block.samples:
            self._w.write(json.dumps(s).encode() + b"\n")  # ", " and ": " separators
            self.n += 1
        return len(block)
    monkeypatch.setattr(storage.BlockWriter, "write_block", spaced)
    _fails(run_tiny(tmp_path, "refine_dedup", n_docs=500), "export_lines_wrong")
