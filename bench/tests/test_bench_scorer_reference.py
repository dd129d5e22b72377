"""A scorer configuration names its reference module, and the harness takes
the reference, its tokens and its FLOP count from that module alone: a second
architecture needs new files under ``bench/`` and no edit."""
import json
import shutil
import sys
import types

import numpy as np
import pytest

from bench import calibrate, check, costs, run
from bench.metrics import scorer_mfu
from bench.reference import mamba2, scorer_reference
from bench.tests.tiny import run_tiny

TOY = "toy_scorer"


@pytest.mark.parametrize("sc, error", [({}, KeyError),
                                       ({"reference": "no_such_arch"}, ModuleNotFoundError),
                                       ({"reference": "text"}, ValueError)])
def test_a_scorer_block_must_name_a_reference(sc, error):
    with pytest.raises(error):
        scorer_reference(sc)


def test_score_mamba2_names_the_mamba2_reference_and_its_flops():
    sc = json.loads((run.ROOT / "bench" / "configs" / "score_mamba2.json").read_text())["scorer"]
    assert scorer_reference(sc) is mamba2
    assert mamba2.flops_per_token(sc) == costs.mamba2_flops_per_token(sc, 256)


def test_mamba2_tiny_is_the_programs_reduced_size():
    assert mamba2.TINY == {"n_layers": 2, "d_model": 64, "vocab_size": 512, "ssm_state": 16,
                           "ssm_headdim": 16, "ssm_chunk": 8, "seq_len": 16, "rows_checked": 16}


def _toy_module(calls):
    """A stand-in architecture: counts of its own, recorded when called."""
    toy = types.ModuleType(f"bench.reference.{TOY}")

    def real_tokens(text, seq):
        calls["real_tokens"] += 1
        return min(len(text) // 7 + 1, seq)

    def row_losses(sc, texts, seq, quantize=None):
        calls["row_losses"].append(quantize)
        return np.linspace(1.0, 2.0, len(texts)) + (0.5 if quantize else 0.0)

    def flops_per_token(sc):
        calls["flops_per_token"] += 1
        return 1000 * sc["toy_width"]

    toy.real_tokens, toy.row_losses, toy.flops_per_token = real_tokens, row_losses, flops_per_token
    toy.TINY = {"seq_len": 16, "rows_checked": 8, "toy_width": 3}
    return toy


def test_a_stand_in_architecture_needs_new_files_only(tmp_path, monkeypatch):
    """A configuration file that names a reference module of its own: the
    tiny config, the check, the control and ``scorer_mfu`` all use that
    module's names, and none of Mamba-2's."""
    calls = {"real_tokens": 0, "row_losses": [], "flops_per_token": 0}
    monkeypatch.setitem(sys.modules, f"bench.reference.{TOY}", _toy_module(calls))

    def not_mamba2(*a, **k):
        raise AssertionError("a Mamba-2 function was called for another architecture")
    for name in ("row_losses", "real_tokens", "flops_per_token"):
        monkeypatch.setattr(mamba2, name, not_mamba2)
    # the benchmark's data files, with one new configuration beside them
    root = tmp_path / "root"
    (root / "bench" / "configs").mkdir(parents=True)
    shutil.copy(run.ROOT / "BENCHMARK.json", root)
    shutil.copytree(run.ROOT / "bench" / "traffic", root / "bench" / "traffic")
    cfg = json.loads((run.ROOT / "bench" / "configs" / "score_mamba2.json").read_text())
    cfg["scorer"]["reference"] = TOY
    (root / "bench" / "configs" / "score_toy.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(run, "ROOT", root)

    detail = {}
    seed = 3_000_000_041
    run_tiny(tmp_path, "score_toy", n_docs=400, seed=seed, detail=detail)
    sc = detail["cfg"]["scorer"]
    assert sc["toy_width"] == 3 and sc["rows_checked"] == 8  # the toy's TINY
    rows = detail["checks"]["scorer_rows"]
    assert rows["ref"] == np.linspace(1.0, 2.0, 8).tolist()
    assert rows["tokens"] == [min(len(t) // 7 + 1, 16) for t in rows["texts"]]
    got = [json.loads(x) for x in check.read_lines(detail["export"])]
    w0, w1 = detail["window_rows"]
    window = sum(min(len(r["text"]) // 7 + 1, 16) for r in got[w0:w1])
    assert detail["checks"]["window_tokens"] == window > 0
    assert calls["row_losses"] == [None]

    control = calibrate.scorer_control(detail, seed)
    assert calls["row_losses"][1] == sc["control_dtype"]
    assert control["scorer_rows"]["ref"] == rows["ref"]
    assert control["numbers"]["scorer_gap_nats"]["value"] == pytest.approx(0.5)

    ctx = {"cfg": detail["cfg"], "checked": detail["checks"],
           "trace": {"module_s": {"jit_score_one": 2.0}}, "peaks": {"bf16_flops_per_s": 1e9}}
    assert scorer_mfu.read(ctx) == pytest.approx(100.0 * 3000 * window / (2.0 * 1e9))
    assert calls["flops_per_token"] == 1
