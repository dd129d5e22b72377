"""The program's annotations on the profiler's clock, and the per-layer
readers that take them from the traced window: ``bench/metrics/_program.py``
and the eight readers built on it."""
import glob
import importlib

import pytest

from bench import run
from bench import trace as tr
from bench.metrics import _program
from bench.tests.tiny import run_tiny

SECONDS_READERS = {
    "dedup_shingle_s_per_kdoc": "dedup.shingle",
    "dedup_sign_s_per_kdoc": "dedup.sign",
    "dedup_index_s_per_kdoc": "dedup.index",
    "stream_wait_s_per_kdoc": "stream.wait",
    "export_s_per_kdoc": "export.write",
    "scorer_encode_s_per_kdoc": "scorer.encode",
    "scorer_step_s_per_kdoc": "scorer.step",
}


def _reader(name):
    return importlib.import_module(f"bench.metrics.{name}")


@pytest.fixture
def clean_obs():
    from repro.core import clock, obs

    obs.reset()
    fake = clock.FakeClock()
    clock.install(fake)
    yield obs, fake
    clock.reset()
    obs.reset()


def _record(obs, fake, trace_dir):
    """A profiler trace of two rounds of nested timed scopes, a span and an
    op annotation, on the fake clock."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with obs.span("t", "run", kind="run"):
            for _ in range(2):
                with obs.timed("scorer.encode"):
                    fake.tick(0.25)
                with obs.timed("scorer.step", {"scorer.tokens_scored": 30,
                                               "scorer.tokens_padded": 10}) as st:
                    fake.tick(0.5)
                    st.add("scorer.rows_total", 4)
            with obs.annotate("op:filter"):
                pass
    finally:
        jax.profiler.stop_trace()


def test_timed_scope_with_the_profiler_on(clean_obs, tmp_path):
    from jax.profiler import ProfileData

    obs, fake = clean_obs
    _record(obs, fake, tmp_path)
    c = obs.metrics().snapshot()["counters"]
    assert c["scorer.encode_seconds"] == pytest.approx(0.5)
    assert c["scorer.step_seconds"] == pytest.approx(1.0)
    assert c["scorer.tokens_padded"] == 20 and c["scorer.rows_total"] == 8
    # the scopes kept no span; only the run span is buffered
    assert [s["name"] for s in obs.drain()] == ["run"]
    assert obs.tracer().dropped == 0
    data = ProfileData.from_file(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                           recursive=True)[-1])
    host = [(e.name, dict(e.stats)) for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    names = [n for n, _ in host]
    assert names.count("scorer.encode") == 2 and names.count("scorer.step") == 2
    assert names.count("run") == 1 and names.count("op:filter") == 1
    assert all(st == {"scorer.tokens_scored": 30, "scorer.tokens_padded": 10,
                      "scorer.rows_total": 4} for n, st in host if n == "scorer.step")


def test_annotations_read_the_runs_trace(clean_obs, tmp_path, monkeypatch):
    obs, fake = clean_obs
    monkeypatch.setattr(run, "WORK", tmp_path)
    _record(obs, fake, tmp_path / "c" / "trace")
    ctx = {"trace": {"busy_s": 0.0}, "cell": {"name": "c"}, "attempted": 2000}
    ann = _program.annotations(ctx)
    assert ann["scorer.step"]["count"] == 2
    assert ann["scorer.step"]["scorer.tokens_scored"] == 60
    assert ann["scorer.step"]["scorer.tokens_padded"] == 20
    assert ann["scorer.encode"]["seconds"] > 0
    assert _reader("scorer_pad_share").read(ctx) == pytest.approx(25.0)
    step = _reader("scorer_step_s_per_kdoc").read(ctx)
    assert step == pytest.approx(ann["scorer.step"]["seconds"] / 2)
    # nothing of a scope the trace lacks, of an untraced run, or of another cell
    assert _reader("dedup_sign_s_per_kdoc").read(ctx) is None
    assert _program.annotations(dict(ctx, trace=None)) is None
    assert _program.annotations(dict(ctx, cell={"name": "other"})) is None


@pytest.mark.parametrize("name", sorted(SECONDS_READERS))
def test_seconds_reader_per_kdoc(name, monkeypatch):
    ann = SECONDS_READERS[name]
    ctx = {"attempted": 4000}
    monkeypatch.setattr(_program, "annotations",
                        lambda c: {ann: {"seconds": 3.0, "count": 7.0}})
    assert _reader(name).read(ctx) == pytest.approx(0.75)
    monkeypatch.setattr(_program, "annotations", lambda c: {"other": {"seconds": 3.0}})
    assert _reader(name).read(ctx) is None
    monkeypatch.setattr(_program, "annotations", lambda c: None)
    assert _reader(name).read(ctx) is None


def test_pad_share_reader(monkeypatch):
    mod = _reader("scorer_pad_share")
    step = {"seconds": 1.0, "count": 2.0, "scorer.tokens_scored": 300.0,
            "scorer.tokens_padded": 100.0}
    monkeypatch.setattr(mod, "annotations", lambda c: {"scorer.step": step})
    assert mod.read({}) == pytest.approx(25.0)
    monkeypatch.setattr(mod, "annotations", lambda c: {"scorer.step": {"seconds": 1.0}})
    assert mod.read({}) is None
    monkeypatch.setattr(mod, "annotations", lambda c: None)
    assert mod.read({}) is None


def test_tiny_traced_refine_run_names_its_work(tmp_path, monkeypatch):
    """A traced tiny ``refine_dedup`` stream: the dedup phases, the stream's
    wait, the export and every op are annotations on a host line of the
    profiler trace, and the readers take them from the window."""
    from repro.core import obs

    obs.reset()
    monkeypatch.setattr(run, "WORK", tmp_path / "work")
    monkeypatch.setattr(run, "peaks_for", lambda kind: {"bf16_flops_per_s": 197e12,
                                                         "hbm_bytes_per_s": 819e9})
    loaded, load_trace = [], tr.load

    def load(trace_dir):
        out = load_trace(trace_dir)
        loaded.append(out)
        return out

    monkeypatch.setattr(tr, "load", load)
    res = run_tiny(tmp_path, "refine_dedup", n_docs=900, seconds=0.2, trace=True)
    assert res["correct"], res["checks"]
    _, host = loaded[0]
    names = {name for name, _, _ in host}
    assert {"dedup.shingle", "dedup.sign", "dedup.index", "stream.wait",
            "export.write"} <= names
    assert {"op:whitespace_normalization_mapper"} <= names
    assert any(n.startswith("op:") and "filter" in n for n in names)
    m = res["metrics"]
    assert {"dedup_shingle_s_per_kdoc", "dedup_sign_s_per_kdoc", "dedup_index_s_per_kdoc",
            "stream_wait_s_per_kdoc", "export_s_per_kdoc"} <= set(m)
    assert not {"scorer_encode_s_per_kdoc", "scorer_step_s_per_kdoc",
                "scorer_pad_share"} & set(m)
    assert obs.tracer().dropped == 0
