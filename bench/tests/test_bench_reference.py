"""Each plain reference against the program at a tiny size on the CPU."""
import json

import numpy as np
import pytest

from bench import traffic
from bench.calibrate import next_token_rows
from bench.reference import mamba2, minhash, text
from bench.tests.tiny import TINY_LIMIT_NATS, tiny_config

SAMPLES = ["plain words only here", "  tabs\tand   runs  \n of   space ", "", "x",
           "!@#$%^&*<>{}[] junk then words words words words words words",
           " ".join(["same five words again now"] * 6)]


def _corpus(tmp_path, name="near_dup", n=500, seed=5):
    mix = traffic.load_mix(name)
    mix["n_docs"] = n
    path = tmp_path / "c.jsonl"
    traffic.generate(mix, seed, str(path))
    return [json.loads(x)["text"] for x in path.read_text().splitlines()]


def test_text_ops_match_the_program(tmp_path):
    from repro.core.registry import create_op

    steps = text.host_steps(tiny_config("refine_dedup")["process"])
    ops = [create_op(dict(s)) for s in steps]
    for t in SAMPLES + _corpus(tmp_path, n=200):
        s = {"text": t, "meta": {}, "stats": {}}
        kept = True
        for op in ops:
            out = op.process_batch([s])
            if not out:
                kept = False
                break
            s = out[0]
        ref_text, ref_stats = text.apply(t, steps)
        assert kept == (ref_stats is not None)
        if kept:
            assert s["text"] == ref_text and s["stats"] == ref_stats


def test_keep_first_matches_the_streaming_kernel_path(tmp_path):
    from repro.core.dedup.streaming import StreamingMinHashState
    from repro.core.storage import SampleBlock

    step = next(s for s in tiny_config("refine_dedup")["process"] if "deduplicator" in s["name"])
    texts = _corpus(tmp_path)
    st = StreamingMinHashState(n_perm=step["num_permutations"], n_bands=step["num_bands"],
                               ngram=step["ngram"], jaccard_threshold=step["jaccard_threshold"],
                               use_kernel=True, super_batch=128)
    blocks = [SampleBlock([{"text": t, "i": i} for i, t in enumerate(texts[k:k + 100], k)])
              for k in range(0, len(texts), 100)]
    kept = [s["i"] for blk, _ in st.stream_blocks(blocks) for s in blk.samples]
    ref = minhash.keep_first(texts, step, max_shingles=512, seed=42)
    assert kept == [i for i, k in enumerate(ref) if k]
    assert len(kept) < len(texts)  # the corpus has duplicates to find


def test_tokenizer_and_weights_match_the_program():
    import jax

    from repro.configs import get_config
    from repro.data.tokenizer import HashWordTokenizer
    from repro.models.model_zoo import build_model

    cfg = tiny_config("score_mamba2")["scorer"]
    tok = HashWordTokenizer(cfg["vocab_size"])
    for t in SAMPLES:
        assert mamba2.tokenize(t, cfg["vocab_size"]) == tok.encode(t)
    params = build_model(get_config("mamba2-1.3b", reduced=True)).init_params(jax.random.PRNGKey(0))
    ref = mamba2.init_weights(cfg)
    flat = dict(params["layers"], embed=params["embed"], final_norm=params["final_norm"])
    assert set(flat) == set(ref)
    for k in ref:
        assert np.array_equal(np.asarray(flat[k]), np.asarray(ref[k])), k


@pytest.fixture(scope="module")
def scored(tmp_path_factory):
    """Program and reference losses of the same rows at the reduced size."""
    import jax

    import repro.models.layers as L
    from repro.configs import get_config
    from repro.data.tokenizer import HashWordTokenizer
    from repro.models.model_zoo import build_model
    from repro.ops.model_ops import batch_scorer

    cfg = tiny_config("score_mamba2")["scorer"]
    texts = _corpus(tmp_path_factory.mktemp("c"), "web", 64)
    model = build_model(get_config("mamba2-1.3b", reduced=True), remat_policy="none")
    params = model.init_params(jax.random.PRNGKey(0))
    # the program's step on next-token targets at every scored position
    batch = next_token_rows(HashWordTokenizer(cfg["vocab_size"]), texts, cfg["seq_len"])
    assert all(np.array_equal(a, b) for a, b in
               zip(batch, mamba2.encode(texts, cfg["vocab_size"], cfg["seq_len"])))
    out = {"ref": mamba2.row_losses(cfg, texts, cfg["seq_len"]),
           "control": mamba2.row_losses(cfg, texts, cfg["seq_len"], quantize="float8_e4m3fn")}
    out["bf16"] = np.asarray(batch_scorer(model)(params, *batch))
    saved = L.COMPUTE_DTYPE
    L.COMPUTE_DTYPE = np.float32  # the program's own math in float32
    try:
        with jax.default_matmul_precision("highest"):
            out["f32"] = np.asarray(batch_scorer(model)(params, *batch))
    finally:
        L.COMPUTE_DTYPE = saved
    return out


def test_mamba2_reference_is_the_program_in_float32(scored):
    assert np.max(np.abs(scored["f32"] - scored["ref"])) < 1e-5
    assert np.ptp(scored["ref"]) > 0.05  # rows differ far more than that


def test_control_fails_where_the_program_passes(scored):
    prog = np.max(np.abs(scored["bf16"] - scored["ref"]))
    ctl = np.max(np.abs(scored["control"] - scored["ref"]))
    assert prog < TINY_LIMIT_NATS < ctl


def test_check_replays_the_same_rows_in_worker_processes(tmp_path):
    from bench import check

    mix = traffic.load_mix("near_dup")
    mix["n_docs"] = 1500
    path = str(tmp_path / "c.jsonl")
    traffic.generate(mix, 2**31 + 9, path)
    cfg = tiny_config("refine_dedup")
    alone = check.expected_rows(cfg, path, 1400, workers=0)
    pooled = check.expected_rows(cfg, path, 1400, workers=2)
    assert alone == pooled
    assert 0 < len(alone["order"]) < len(alone["passed"]) < 1400
