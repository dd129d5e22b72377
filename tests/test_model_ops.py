"""``lm_perplexity_filter`` on reduced configs: the scored length respects a
chunked SSM's chunk, and per-row scores match the model's ``loss_fn`` on
each row alone."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.registry import create_op
from repro.data.synthetic import make_corpus


def _scored(op, samples):
    out = op.run_batch_safe([dict(s) for s in samples])
    assert not op.errors, op.errors
    return np.array([s["stats"]["lm_ppl"] for s in out])


def _row_losses(op, texts):
    """Reference: the model's loss_fn on each row alone, tokenized exactly
    as the op's batch is."""
    from repro.ops.model_ops import encode_rows

    toks, labels, mask = encode_rows(op._tok, texts, op._seq)
    return np.array([
        float(op._model.loss_fn(op._params, {
            "tokens": jnp.asarray(toks[i:i + 1]),
            "labels": jnp.asarray(labels[i:i + 1]),
            "loss_mask": jnp.asarray(mask[i:i + 1])})[0])
        for i in range(len(texts))])


@pytest.mark.parametrize("arch,seq_len,scored_len", [
    ("mamba2-1.3b", 12, 16),  # reduced chunk 8 does not divide 12: rounds up
    ("mamba2-1.3b", 16, 16),  # chunk divides: unchanged
    ("phi3-medium-14b", 12, 12),  # no chunked scan: unchanged
])
def test_lm_perplexity_scored_length(arch, seq_len, scored_len):
    samples = make_corpus(10, seed=3)
    op = create_op({"name": "lm_perplexity_filter", "arch": arch,
                    "reduced": True, "seq_len": seq_len})
    ppl = _scored(op, samples)
    assert op._seq == scored_len
    assert len(ppl) == len(samples) and np.all(np.isfinite(ppl))
    ref = _row_losses(op, [s["text"] for s in samples])
    np.testing.assert_allclose(np.log(ppl), ref, rtol=1e-5, atol=1e-5)


def test_lm_perplexity_rounded_length_scores_as_requested_multiple():
    """A seq_len the chunk does not divide scores exactly as the multiple
    it rounds up to."""
    samples = make_corpus(10, seed=4)
    ops = [create_op({"name": "lm_perplexity_filter", "reduced": True,
                      "seq_len": n}) for n in (9, 16)]
    a, b = (_scored(op, samples) for op in ops)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("words,seq", [(5, 16), (40, 16)])  # a short row, a cut row
def test_encode_rows_targets_are_the_next_ids(words, seq):
    """Every scored position's target is the id after it: the next word, EOS
    after the last word of a short row, the first word past the cut of a
    long one; never the pad id."""
    from repro.data.tokenizer import HashWordTokenizer
    from repro.ops.model_ops import encode_rows

    tok = HashWordTokenizer(512)
    text = " ".join(f"w{i}" for i in range(words))
    ids = tok.encode(text)
    toks, labels, mask = encode_rows(tok, [text], seq)
    n = int(mask[0].sum())
    assert n == min(len(ids) - 1, seq)
    np.testing.assert_array_equal(toks[0, :n], ids[:n])
    np.testing.assert_array_equal(labels[0, :n], ids[1:n + 1])
    assert labels[0, n - 1] == (tok.EOS if words + 1 < seq else ids[seq])
    assert labels[0, n - 1] != tok.PAD
