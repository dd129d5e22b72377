"""granite-4.0-h-small's share against its plain reference on the CPU at the
program's reduced size, its configuration file against the program's, a
tiny whole run of its cell, and ``scorer_mfu``'s FLOPs and
``scorer_moe_roofline``'s reading."""
import json

import numpy as np
import pytest

from bench import costs_granite4h, run, traffic
from bench.calibrate import next_token_rows
from bench.metrics import scorer_moe_roofline
from bench.reference import granite4h, scorer_reference
from bench.tests.tiny import run_tiny, tiny_config

# Program (bfloat16 parameters and compute, the configuration's precision;
# a float32 residual stream and float32 router logits) against the reference
# on 64 web rows at the reduced size: 0.0087 nats (median row 0.0016); the
# float8 control: 0.0997; the held experts' part zeroed: 0.164; experts 1-2
# held in place of 0-1: 0.189 (CPU). The tolerance lies between the program
# and the nearest of the others, with room on both sides.
TINY_TOL_NATS = 0.03


def _sc():
    return json.loads((run.ROOT / "bench" / "configs" / "score_granite4h.json").read_text())


def test_config_names_the_reference_and_the_programs_share():
    from repro.configs import get_config

    cfg = _sc()
    sc = cfg["scorer"]
    assert scorer_reference(sc) is granite4h
    prog = get_config(sc["arch"])
    assert (prog.n_layers, list(prog.layer_pattern)) == (len(sc["layer_types"]), sc["layer_types"])
    assert (prog.d_model, prog.n_heads, prog.n_kv_heads) == (
        sc["hidden_size"], sc["num_attention_heads"], sc["num_key_value_heads"])
    assert (prog.ssm_nheads, prog.ssm_headdim, prog.ssm_state, prog.ssm_chunk) == (
        sc["mamba_n_heads"], sc["mamba_d_head"], sc["mamba_d_state"], sc["mamba_chunk_size"])
    assert (prog.n_experts, prog.top_k, list(prog.experts_held)) == (
        sc["router_experts"], sc["num_experts_per_tok"], sc["experts_held"])
    assert (prog.d_ff, prog.shared_d_ff, prog.vocab_size) == (
        sc["intermediate_size"], sc["shared_intermediate_size"], sc["vocab_size"])
    assert (prog.embedding_multiplier, prog.residual_multiplier, prog.attention_multiplier,
            prog.logits_scaling, prog.use_rope, prog.param_dtype) == (
        sc["embedding_multiplier"], sc["residual_multiplier"], sc["attention_multiplier"],
        sc["logits_scaling"], False, sc["param_dtype"])
    # the catalog's keys at the top level: the share's cuts, the rest as published
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"], cfg["vocab_size"]) == (10, 9, 12544)
    assert cfg["layer_types"] == sc["layer_types"] and cfg["intermediate_size"] == 768


def test_tiny_is_the_programs_reduced_size():
    from repro.configs import get_config

    c = get_config("granite-4.0-h-small-ep8", reduced=True)
    t = granite4h.TINY
    assert (t["layer_types"], t["hidden_size"], t["num_attention_heads"],
            t["num_key_value_heads"], t["mamba_d_head"], t["mamba_n_heads"], t["mamba_d_state"],
            t["mamba_chunk_size"], t["intermediate_size"], t["shared_intermediate_size"],
            t["router_experts"], t["num_experts_per_tok"], t["experts_held"], t["vocab_size"]) == (
        list(c.layer_pattern), c.d_model, c.n_heads, c.n_kv_heads, c.ssm_headdim, c.ssm_nheads,
        c.ssm_state, c.ssm_chunk, c.d_ff, c.shared_d_ff, c.n_experts, c.top_k,
        list(c.experts_held), c.vocab_size)
    assert len(t["experts_held"]) < t["num_experts_per_tok"] < t["router_experts"]


@pytest.fixture(scope="module")
def scored(tmp_path_factory):
    """Program and reference losses of the same rows at the reduced size."""
    import dataclasses

    import jax

    import repro.models.layers as L
    from repro.configs import get_config
    from repro.data.tokenizer import HashWordTokenizer
    from repro.models.model_zoo import build_model
    from repro.ops.model_ops import batch_scorer

    sc = tiny_config("score_granite4h")["scorer"]
    mix = traffic.load_mix("web")
    mix["n_docs"] = 64
    path = tmp_path_factory.mktemp("c") / "c.jsonl"
    traffic.generate(mix, 5, str(path))
    texts = [json.loads(x)["text"] for x in path.read_text().splitlines()]
    cfg = get_config(sc["arch"], reduced=True)
    model = build_model(cfg, remat_policy="none")
    params = model.init_params(jax.random.PRNGKey(0))
    batch = next_token_rows(HashWordTokenizer(sc["vocab_size"]), texts, sc["seq_len"])
    out = {"sc": sc, "cfg": cfg, "model": model, "params": params,
           "ref": granite4h.row_losses(sc, texts, sc["seq_len"]),
           "control": granite4h.row_losses(sc, texts, sc["seq_len"],
                                            quantize=sc["control_dtype"]),
           "bf16": np.asarray(batch_scorer(model)(params, *batch))}
    # planted faults: the held experts' part of every layer zeroed, and a
    # chip that holds experts 1-2 where the configuration says 0-1
    from repro.models import granite_hybrid

    def no_moe(x, *a):
        out, slots = moe_share(x, *a)
        return out * 0, slots

    moe_share = granite_hybrid.moe_share
    granite_hybrid.moe_share = no_moe
    try:
        out["moe_zeroed"] = np.asarray(batch_scorer(build_model(cfg, remat_policy="none"))(
            params, *batch))
    finally:
        granite_hybrid.moe_share = moe_share
    wrong = build_model(dataclasses.replace(cfg, experts_held=(1, 2)), remat_policy="none")
    out["wrong_held"] = np.asarray(batch_scorer(wrong)(wrong.init_params(jax.random.PRNGKey(0)),
                                                       *batch))
    m32 = build_model(dataclasses.replace(cfg, param_dtype="float32"), remat_policy="none")
    saved = L.COMPUTE_DTYPE
    L.COMPUTE_DTYPE = np.float32  # the program's own math in float32
    try:
        with jax.default_matmul_precision("highest"):
            out["f32"] = np.asarray(batch_scorer(m32)(m32.init_params(jax.random.PRNGKey(0)),
                                                      *batch))
    finally:
        L.COMPUTE_DTYPE = saved
    return out


def test_weights_are_the_references_rule(scored):
    import jax.numpy as jnp

    model, params = scored["model"], scored["params"]
    ref = granite4h.init_weights(scored["sc"])

    def same(prog, want):
        return np.array_equal(np.asarray(prog, np.float32),
                              np.asarray(want.astype(jnp.bfloat16), np.float32))
    assert same(params["embed"], ref["embed"])
    for i, kind in enumerate(scored["cfg"].layer_pattern):
        j = model.layers_of[kind].index(i)
        assert set(ref["layers"][i]) == set(params[kind])
        for name, want in ref["layers"][i].items():
            assert same(params[kind][name][j], want), (i, name)


def test_reference_is_the_program_in_float32(scored):
    assert np.max(np.abs(scored["f32"] - scored["ref"])) < 1e-5


def test_program_within_a_tolerance_the_control_and_faults_fail(scored):
    gap = {k: float(np.max(np.abs(scored[k] - scored["ref"])))
           for k in ("bf16", "control", "moe_zeroed", "wrong_held")}
    assert gap["bf16"] < TINY_TOL_NATS < min(gap["control"], gap["moe_zeroed"], gap["wrong_held"]), gap


def test_tiny_whole_run_is_correct_and_counts_the_held_rows(tmp_path, monkeypatch):
    """A traced tiny run: ``correct``, the readers of the program's
    annotations read, and the step counted the held experts' rows (no
    device trace on the CPU, so the kernel's share stays silent)."""
    from bench.tests import tiny
    from repro.core import obs

    obs.reset()
    # tiny.py's limit (0.005) is set from the Mamba-2 scorer's readings at
    # its reduced size; this model's readings at its own are above
    # (TINY_TOL_NATS): the limit that lies between them
    monkeypatch.setattr(tiny, "TINY_LIMIT_NATS", TINY_TOL_NATS)
    monkeypatch.setattr(run, "WORK", tmp_path / "work")
    monkeypatch.setattr(run, "peaks_for", lambda kind: {"bf16_flops_per_s": 197e12,
                                                         "hbm_bytes_per_s": 819e9})
    res = run_tiny(tmp_path, "score_granite4h", n_docs=300, seed=3_000_000_061, trace=True)
    assert res["correct"], res["checks"]
    assert "scorer_pad_share" in res["metrics"] and "scorer_step_s_per_kdoc" in res["metrics"]
    assert "scorer_moe_roofline" not in res["metrics"]
    c = obs.metrics().snapshot()["counters"]
    # each scored position sends top_k = 3 assignments per layer (4 layers)
    # over 8 experts, 2 of them held: the held share is near 2/8
    share = c["scorer.moe_rows"] / (4 * 3 * c["scorer.tokens_scored"])
    assert 0.12 < share < 0.4, share
    assert 0 < c["scorer.moe_rows_max"] < c["scorer.moe_rows"]


def test_flops_per_token_by_hand_at_the_published_widths():
    sc = _sc()["scorer"]
    d, f, fs, v = 4096, 768, 1536, 12544
    mamba = (2 * d * (2 * 8192 + 2 * 128 + 128) + 2 * 8448 * 4 + 2 * 256 * 128
             + 2 * 256 * 64 * 128 + 4 * 128 * 64 * 128 + 2 * 8192 * d)
    attention = 2 * d * (4096 + 2 * 1024) + 2 * 4096 * d + 2 * 256 * 4096
    experts = 2 * d * 72 + 6 * d * fs + 10 * 9 * 6 * d * f // 72  # 1.25 held expert FFNs
    want = 9 * mamba + attention + 10 * experts + 2 * d * v
    assert costs_granite4h.flops_per_token(sc) == want == granite4h.flops_per_token(sc)
    assert 2.7e9 < want < 2.75e9
    assert costs_granite4h.expert_row_flops(sc) == 6 * d * f


def test_moe_roofline_reads_a_hand_made_summary(monkeypatch):
    sc = _sc()["scorer"]
    ann = {"scorer.step": {"seconds": 9.0, "scorer.moe_rows": 2.0e6}}
    monkeypatch.setattr(scorer_moe_roofline, "annotations", lambda ctx: ann)
    ctx = {"cfg": {"scorer": sc}, "peaks": {"bf16_flops_per_s": 197e12},
           "trace": {"ops": [["jit_score_one/while", 30.0], ["jit_score_one/custom-call", 1.5],
                             ["jit_score_one/fusion", 20.0]]}}
    want = 100.0 * 2.0e6 * 6 * 4096 * 768 / (1.5 * 197e12)
    assert scorer_moe_roofline.read(ctx) == pytest.approx(want)
    # the kernel filed under its instruction's name reads the same
    ctx["trace"]["ops"] = [["jit_score_one/while", 30.0], ["jit_score_one/gmm", 1.5]]
    assert scorer_moe_roofline.read(ctx) == pytest.approx(want)
    # a trace without the kernel, or a program without the counts: nothing
    ctx["trace"]["ops"] = [["jit_score_one/while", 30.0]]
    assert scorer_moe_roofline.read(ctx) is None
    ctx["trace"]["ops"] = [["jit_score_one/custom-call", 1.5]]
    ann["scorer.step"] = {"seconds": 9.0}
    assert scorer_moe_roofline.read(ctx) is None
