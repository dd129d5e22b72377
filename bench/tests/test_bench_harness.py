"""The harness end to end at a tiny size on the CPU, with the device check
skipped; and its refusals."""
import json
import os
import shutil
import subprocess
import sys

from bench import run
from bench.calibrate import repaired_labels
from bench.tests.tiny import run_tiny


def _run_cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "refine_dedup.web",
                           "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run_cli(run.ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr and not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and not p.stdout.strip()


def test_tiny_refine_run_is_correct(tmp_path):
    detail = {}
    res = run_tiny(tmp_path, "refine_dedup", n_docs=900, seconds=0.2, detail=detail)
    assert res["correct"], res["checks"]
    # every signature dispatch's programs were warmed in set-up; only the
    # tail of a corpus that runs out, which no cell reaches, holds fewer
    # documents than a super-batch
    assert not detail["exhausted"]
    assert detail["window_compiles"] == []
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"docs_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["dedup_rows_wrong"] == {"value": 0, "limit": 0}
    json.dumps(res)


def test_tiny_traced_scored_run(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "peaks_for", lambda kind: {"bf16_flops_per_s": 197e12,
                                                         "hbm_bytes_per_s": 819e9})
    # the op's last-position pad target repaired (see test_bench_faults)
    with repaired_labels():
        res = run_tiny(tmp_path, "score_mamba2", n_docs=400, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # program spans and counters are read on the CPU; the device trace has no
    # TPU plane here, so its readers find nothing and stay silent
    assert {"plan_probe_s", "host_ops_s_per_kdoc", "dedup_s_per_kdoc",
            "scorer_s_per_kdoc"} <= set(m)
    assert not {"device_idle_share", "minhash_hbm_roofline", "scorer_mfu"} & set(m)
    assert res["device"]["window_s"] > 0 and "breakdown" in res
    assert res["checks"]["scorer_gap_nats"]["value"] < 0.05
