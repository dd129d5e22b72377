"""Observability units (repro.core.obs + repro.core.clock +
repro.api.slo): fake-clock hermeticity, span lifecycle/tree/merge,
Chrome-trace export, bounded metrics + cross-process merge, SLO math, the
executor/dispatcher trace surfaces, shards="auto" resolution, and the
clock-discipline lint."""
import json
import os
import subprocess
import sys

import pytest

from repro.api.slo import compute_slo, percentile
from repro.core import clock, obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    clock.reset()
    yield
    obs.reset()
    clock.reset()


@pytest.fixture
def fake():
    fc = clock.FakeClock()
    clock.install(fc)
    yield fc
    clock.reset()


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------


def test_fake_clock_advances_wall_and_monotonic_together(fake):
    w0, m0 = clock.now(), clock.monotonic()
    fake.tick(2.5)
    assert clock.now() == pytest.approx(w0 + 2.5)
    assert clock.monotonic() == pytest.approx(m0 + 2.5)
    clock.reset()
    assert clock.now() != pytest.approx(w0 + 2.5)  # back on the system clock


def test_clock_lint_is_clean_and_catches_violations(tmp_path):
    tool = os.path.join(REPO, "tools", "check_clock.py")
    ok = subprocess.run([sys.executable, tool,
                         os.path.join(REPO, "src", "repro")],
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr

    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "mod.py").write_text(
        "import time\nfrom time import monotonic\n"
        "def f():\n    return time.time() + monotonic()\n")
    hit = subprocess.run([sys.executable, tool, str(bad)],
                         capture_output=True, text=True)
    assert hit.returncode == 1
    assert "time.time()" in hit.stdout and "from time import monotonic" in hit.stdout


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_lifecycle_and_ambient_stack(fake):
    with obs.span("t1", "outer", kind="run") as sp:
        assert obs.current_span() is sp
        fake.tick(1.0)
        with obs.span("t1", "inner", kind="op") as child:
            assert child.parent_id == sp.span_id
            fake.tick(0.5)
    assert obs.current_span() is None
    spans = obs.drain("t1")
    by_name = {s["name"]: s for s in spans}
    assert by_name["outer"]["dur"] == pytest.approx(1.5)
    assert by_name["inner"]["dur"] == pytest.approx(0.5)
    tree = obs.span_tree(spans)
    assert len(tree["roots"]) == 1 and tree["orphans"] == []


def test_start_span_returns_none_when_disabled_or_traceless():
    assert obs.start_span(None, "x") is None
    obs.disable()
    try:
        assert obs.start_span("t", "x") is None
        with obs.span("t", "x") as sp:
            assert sp is None
    finally:
        obs.enable()
    assert obs.drain() == []


def test_span_end_is_idempotent(fake):
    sp = obs.start_span("t", "once")
    fake.tick(1.0)
    sp.end()
    fake.tick(5.0)
    sp.end()  # second end must not re-record or restamp
    spans = obs.drain("t")
    assert len(spans) == 1 and spans[0]["dur"] == pytest.approx(1.0)


def test_span_buffer_is_bounded(fake):
    for i in range(obs.MAX_SPANS + 10):
        obs.start_span("t", f"s{i}").end()
    assert len(obs.drain()) == obs.MAX_SPANS
    assert obs.tracer().dropped == 10


def test_merge_spans_dedupes_reexecuted_span_ids(fake):
    a1 = {"trace_id": "t", "span_id": "A", "parent_id": None,
          "name": "job", "kind": "job", "t0": 1.0, "dur": 0.5,
          "pid": 1, "tid": 0, "attrs": {"attempt": 1}}
    a2 = dict(a1, dur=2.0, attrs={"attempt": 2})  # re-lease re-emits A
    b = dict(a1, span_id="B", parent_id="A", t0=1.2, dur=0.1, attrs={})
    merged = obs.merge_spans([a1, b, a2])
    assert [s["span_id"] for s in merged] == ["A", "B"]
    assert merged[0]["attrs"]["attempt"] == 2, "last-writer (longer dur) wins"


def test_spill_and_merge_trace_roundtrip(fake, tmp_path):
    d = str(tmp_path / "obs")
    obs.configure(d)
    obs.start_span("t1", "root", kind="job").end()
    obs.start_span("t2", "other-trace").end()
    obs.flush()
    obs.flush()  # empty buffer: must not duplicate
    spans = obs.merge_trace(d, "t1")
    assert [s["name"] for s in spans] == ["root"]
    # torn tail line from a SIGKILLed process is skipped, not fatal
    spill = [f for f in os.listdir(d) if f.startswith("spans-")][0]
    with open(os.path.join(d, spill), "ab") as f:
        f.write(b'{"trace_id": "t1", "span')
    assert [s["name"] for s in obs.merge_trace(d, "t1")] == ["root"]


def test_chrome_trace_is_valid_catapult(fake):
    obs.start_span("t", "root", kind="job").set(n=1).end()
    doc = obs.chrome_trace(obs.drain("t"))
    doc = json.loads(json.dumps(doc))  # JSON-serializable end to end
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 1 and len(ms) == 1
    ev = xs[0]
    assert ev["name"] == "root" and ev["cat"] == "job"
    assert ev["dur"] > 0 and {"ts", "pid", "tid", "args"} <= set(ev)
    assert ev["args"]["trace_id"] == "t" and ev["args"]["n"] == 1


# ---------------------------------------------------------------------------
# timed scopes and profiler annotations
# ---------------------------------------------------------------------------


def _nested_scopes(fake):
    """Two rounds of an outer scope (1.0 s of its own) around an inner one
    (0.5 s, with counts): the inner's time leaves the outer's counter."""
    for _ in range(2):
        with obs.timed("t.outer"):
            fake.tick(0.25)
            with obs.timed("t.inner", {"t.rows_total": 3}) as inner:
                fake.tick(0.5)
                inner.add("t.kept_total", True)
            fake.tick(0.75)


def test_timed_scope_counters_add_up(fake):
    _nested_scopes(fake)
    c = obs.metrics().snapshot()["counters"]
    assert c["t.outer_seconds"] == pytest.approx(2.0)
    assert c["t.inner_seconds"] == pytest.approx(1.0)
    assert c["t.rows_total"] == 6.0 and c["t.kept_total"] == 2.0
    json.dumps(c)  # plain floats, whatever the counts were given as


def test_timed_scope_buffers_no_span(fake):
    with obs.span("t", "run", kind="run"):
        for _ in range(obs.MAX_SPANS + 10):
            with obs.timed("t.block"):
                fake.tick(0.001)
    assert [s["name"] for s in obs.drain()] == ["run"]
    assert obs.tracer().dropped == 0
    c = obs.metrics().snapshot()["counters"]
    assert c["t.block_seconds"] == pytest.approx((obs.MAX_SPANS + 10) * 0.001)


def test_timed_scope_disabled_counts_nothing(fake):
    obs.disable()
    _nested_scopes(fake)
    assert obs.metrics().snapshot()["counters"] == {}


def test_obs_does_not_import_jax():
    code = ("import sys\n"
            "from repro.core import obs, storage\n"
            "with obs.span('t', 'run'):\n"
            "    with obs.timed('x.step', {'x.rows_total': 2}):\n"
            "        with obs.annotate('op:y'):\n"
            "            pass\n"
            "assert obs.metrics().snapshot()['counters']['x.rows_total'] == 2\n"
            "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_metrics_snapshot_merge_and_percentile(tmp_path):
    m = obs.MetricsRegistry()
    m.inc("jobs_total", 2)
    m.gauge_max("peak_bytes", 100)
    m.gauge_max("peak_bytes", 50)  # max-merge: stays 100
    for v in (0.002, 0.002, 0.3, 0.3):
        m.observe("wait_seconds", v)
    snap = m.snapshot()
    assert snap["counters"]["jobs_total"] == 2
    assert snap["gauges"]["peak_bytes"] == 100
    h = snap["histograms"]["wait_seconds"]
    assert h["count"] == 4 and sum(h["counts"]) == 4

    other = {"counters": {"jobs_total": 3}, "gauges": {"peak_bytes": 70},
             "histograms": {"wait_seconds": dict(h)}, "dropped": 1}
    merged = obs.MetricsRegistry.merge([snap, other])
    assert merged["counters"]["jobs_total"] == 5
    assert merged["gauges"]["peak_bytes"] == 100
    assert merged["histograms"]["wait_seconds"]["count"] == 8
    assert merged["dropped"] == 1
    p50 = obs.histogram_percentile(merged["histograms"]["wait_seconds"], 0.5)
    p95 = obs.histogram_percentile(merged["histograms"]["wait_seconds"], 0.95)
    assert p50 <= 0.005 and p95 == pytest.approx(0.5), \
        "upper-edge rule: half the samples in the 5ms bucket, rest in 0.5s"


def test_metrics_registry_is_bounded():
    m = obs.MetricsRegistry()
    for i in range(obs.MAX_METRICS + 5):
        m.inc(f"c{i}")
    assert len(m.snapshot()["counters"]) == obs.MAX_METRICS
    assert m.dropped == 5
    m.inc("c0")  # existing names still update past the cap
    assert m.snapshot()["counters"]["c0"] == 2


def test_metrics_spill_files_merge_across_processes(tmp_path):
    d = str(tmp_path / "obs")
    m = obs.MetricsRegistry()
    m.inc("x")
    os.makedirs(d, exist_ok=True)
    m.flush(os.path.join(d, "metrics-111.json"))
    m.inc("x")
    m.flush(os.path.join(d, "metrics-222.json"))
    merged = obs.merged_metrics(d)
    assert merged["counters"]["x"] == 3  # 1 + 2 across "processes"


# ---------------------------------------------------------------------------
# SLO math
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    assert percentile([], 0.5) == 0.0
    assert percentile([3.0], 0.95) == 3.0
    xs = [float(i) for i in range(1, 11)]
    assert percentile(xs, 0.0) == 1.0
    assert percentile(xs, 0.5) == 5.0
    assert percentile(xs, 1.0) == 10.0


def test_percentile_even_length_true_nearest_rank():
    # the old int(round(q*(n-1))) formula hit Python's banker's rounding on
    # even-length inputs: round(1.5) == 2 gave p50([1,2,3,4]) == 3.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
    assert percentile([1.0, 2.0], 0.5) == 1.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.95) == 4.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.25) == 1.0
    # nearest-rank p95 over 20 values is the 19th (index 18), not the 20th
    xs = [float(i) for i in range(1, 21)]
    assert percentile(xs, 0.95) == 19.0


def test_shard_task_predicate_is_strict():
    from repro.api.cluster import is_shard_task, parent_of
    from repro.api import shards

    # the reserved grammar
    for tid in ("job~s0", "job~s12", "job~r3", "job~fin", "a~s1~r2"):
        assert is_shard_task(tid), tid
    assert parent_of("job~s0") == "job"
    assert parent_of("a~s1~r2") == "a~s1"
    # user jobs that merely contain '~' are PLAIN jobs (the old `"~" in id`
    # predicate silently dropped them from SLO queue-wait counts)
    for jid in ("nightly~v2", "job~", "job~rat", "job~final", "job~s",
                "job~r", "job~s1b", None, ""):
        assert not is_shard_task(jid), jid
    assert parent_of("nightly~v2") == "nightly~v2"
    # shards.py re-exports the same predicate — one grammar, both modules
    assert shards.is_shard_task is is_shard_task


def test_compute_slo_counts_user_jobs_with_tilde():
    evs = [
        {"event": "submitted", "job_id": "nightly~v2", "ts": 10.0},
        {"event": "claimed", "job_id": "nightly~v2", "ts": 11.0,
         "runner_id": "r1"},
        {"event": "finished", "job_id": "nightly~v2", "ts": 12.0,
         "runner_id": "r1", "state": "succeeded", "n_out": 5, "seconds": 1.0},
    ]
    s = compute_slo(evs)
    assert s["queue_wait"]["n"] == 1
    assert s["queue_wait"]["p50"] == pytest.approx(1.0)
    assert s["jobs_finished"] == 1


def test_compute_slo_folds_event_log():
    evs = [
        {"event": "submitted", "job_id": "a", "ts": 10.0},
        {"event": "claimed", "job_id": "a", "ts": 10.5, "runner_id": "r1"},
        {"event": "submitted", "job_id": "b", "ts": 11.0},
        {"event": "claimed", "job_id": "b", "ts": 13.0, "runner_id": "r2"},
        {"event": "requeued_after_expiry", "job_id": "b", "ts": 14.0},
        # second claim after failover must NOT reset b's queue-wait
        {"event": "claimed", "job_id": "b", "ts": 14.5, "runner_id": "r1"},
        {"event": "finished", "job_id": "a", "ts": 20.0, "runner_id": "r1",
         "state": "succeeded", "n_out": 100, "seconds": 2.0,
         "redispatches": 1, "preempted": 0},
        {"event": "finished", "job_id": "b", "ts": 25.0, "runner_id": "r1",
         "state": "failed", "n_out": 0, "seconds": 1.0, "preempted": 2},
        # shard task: counts toward runner throughput, not queue-wait
        {"event": "submitted", "job_id": "b~s0", "ts": 14.6},
        {"event": "claimed", "job_id": "b~s0", "ts": 20.0, "runner_id": "r2"},
        {"event": "finished", "job_id": "b~s0", "ts": 24.0, "runner_id": "r2",
         "state": "succeeded", "n_out": 25, "seconds": 0.5},
    ]
    s = compute_slo(evs)
    assert s["queue_wait"]["n"] == 2
    assert s["queue_wait"]["p50"] == pytest.approx(0.5)
    assert s["queue_wait"]["p95"] == pytest.approx(2.0)
    assert s["failovers"] == 1 and s["preempted"] == 2
    assert s["jobs_finished"] == 2 and s["jobs_failed"] == 1
    assert s["throughput"]["r1"]["jobs"] == 2
    assert s["throughput"]["r2"]["rows"] == 25
    assert s["throughput"]["r2"]["rows_per_second"] == pytest.approx(50.0)


def test_compute_slo_per_tenant_breakdowns():
    evs = [
        {"event": "submitted", "job_id": "a", "ts": 0.0, "tenant": "alice"},
        {"event": "claimed", "job_id": "a", "ts": 1.0, "runner_id": "r1"},
        {"event": "submitted", "job_id": "b", "ts": 0.0, "tenant": "bob"},
        {"event": "claimed", "job_id": "b", "ts": 4.0, "runner_id": "r1"},
        # legacy event without a tenant field folds into the default tenant
        {"event": "submitted", "job_id": "c", "ts": 0.0},
        {"event": "claimed", "job_id": "c", "ts": 2.0, "runner_id": "r1"},
        {"event": "finished", "job_id": "a", "ts": 5.0, "runner_id": "r1",
         "state": "succeeded", "n_out": 40, "seconds": 2.0},
        {"event": "finished", "job_id": "b", "ts": 9.0, "runner_id": "r1",
         "state": "failed", "n_out": 0, "seconds": 1.0},
        # alice's shard task: rows fold into ALICE's throughput (via the
        # parent), never into queue-wait
        {"event": "submitted", "job_id": "a~s0", "ts": 5.0, "tenant": "alice"},
        {"event": "claimed", "job_id": "a~s0", "ts": 6.0, "runner_id": "r1"},
        {"event": "finished", "job_id": "a~s0", "ts": 8.0, "runner_id": "r1",
         "state": "succeeded", "n_out": 10, "seconds": 1.0},
    ]
    s = compute_slo(evs)
    t = s["tenants"]
    assert set(t) == {"alice", "bob", "default"}
    assert t["alice"]["queue_wait"]["n"] == 1
    assert t["alice"]["queue_wait"]["p95"] == pytest.approx(1.0)
    assert t["alice"]["jobs_finished"] == 1 and t["alice"]["jobs_failed"] == 0
    assert t["alice"]["rows"] == 50  # parent 40 + shard task 10
    assert t["alice"]["rows_per_second"] == pytest.approx(50 / 3.0)
    assert t["bob"]["queue_wait"]["p50"] == pytest.approx(4.0)
    assert t["bob"]["jobs_failed"] == 1
    assert t["default"]["queue_wait"]["n"] == 1
    # cluster-wide view is unchanged by the breakdown
    assert s["queue_wait"]["n"] == 3


def test_compute_slo_requeued_failover_job(fake, tmp_path):
    """A job claimed, lease-expired, and re-claimed counts ONE queue wait
    (submit -> FIRST claim) and one failover — driven through the real
    ClusterQueue event log under the fake clock, not a hand-built fixture."""
    from repro.api.cluster import ClusterQueue

    q = ClusterQueue(str(tmp_path / "cluster"), lease_ttl=1.0)
    jid = q.submit({"name": "r"}, job_id="flaky")
    fake.tick(2.0)
    lease1 = q.try_claim(jid, "r1", ttl=1.0)
    assert lease1 is not None and lease1.attempt == 1
    fake.tick(5.0)  # r1 dies: lease expires without a heartbeat
    lease2 = q.try_claim(jid, "r2", ttl=1.0)
    assert lease2 is not None and lease2.attempt == 2
    fake.tick(3.0)
    assert q.complete(lease2, "succeeded",
                      report={"n_out": 7, "seconds": 3.0})
    s = compute_slo(q.read_log())
    assert s["queue_wait"]["n"] == 1, "one wait despite two claims"
    assert s["queue_wait"]["max"] == pytest.approx(2.0), \
        "wait is submit -> FIRST claim; the re-claim is failover, not wait"
    assert s["failovers"] == 1
    assert s["jobs_finished"] == 1 and s["jobs_failed"] == 0
    assert s["throughput"]["r2"]["rows"] == 7
    assert s["tenants"]["default"]["jobs_finished"] == 1


# ---------------------------------------------------------------------------
# executor / dispatcher surfaces
# ---------------------------------------------------------------------------


def _run(tmp_path, engine="local", **kw):
    from repro.core.executor import Executor
    from repro.core.recipes import Recipe
    from repro.core.storage import write_jsonl
    from repro.data.synthetic import make_corpus

    src = str(tmp_path / "in.jsonl")
    write_jsonl(src, make_corpus(60, seed=3))
    r = Recipe(name="obs-run", dataset_path=src,
               export_path=str(tmp_path / "out.jsonl"),
               process=[{"name": "whitespace_normalization_mapper"},
                        {"name": "text_length_filter", "min_val": 1}],
               engine=engine, use_fusion=False, use_reordering=False, **kw)
    return Executor(r).run()


def test_run_report_carries_trace_with_op_spans(tmp_path):
    _, rep = _run(tmp_path)
    tr = rep.trace
    assert tr and tr["trace_id"] and tr["root_span"]
    spans = tr["spans"]
    kinds = sorted(s["kind"] for s in spans)
    # ops get no synthesized spans: their times are the per-op rows, and
    # their per-block work carries op:<name> profiler annotations
    assert kinds.count("run") == 1 and "op" not in kinds
    assert [row["op"] for row in rep.per_op] == [
        "whitespace_normalization_mapper", "text_length_filter"]
    tree = obs.span_tree(spans)
    assert tree["roots"] == [tr["root_span"]] and tree["orphans"] == []


def test_parallel_run_ships_block_spans_over_ipc(tmp_path):
    _, rep = _run(tmp_path, engine="parallel", np=2, block_bytes=2000)
    spans = rep.trace["spans"]
    kinds = {s["kind"] for s in spans}
    assert {"run", "dispatch", "block"} <= kinds and "op" not in kinds
    blocks = [s for s in spans if s["kind"] == "block"]
    dispatch = [s for s in spans if s["kind"] == "dispatch"]
    assert all(b["parent_id"] == dispatch[0]["span_id"] for b in blocks), \
        "worker-side block spans must parent to the driver's dispatch span"
    assert all("queue_wait" in b["attrs"] for b in blocks)
    assert obs.span_tree(spans)["orphans"] == []
    snap = obs.metrics().snapshot()
    assert snap["counters"].get("dispatch.blocks_total", 0) >= len(blocks)
    assert "dispatch.queue_wait_seconds" in snap["histograms"]


def test_tracing_disabled_run_has_no_trace(tmp_path):
    obs.disable()
    try:
        _, rep = _run(tmp_path)
        assert rep.trace is None
        assert obs.drain() == []
    finally:
        obs.enable()


# ---------------------------------------------------------------------------
# shards="auto"
# ---------------------------------------------------------------------------


def test_resolve_shard_count_auto_by_rows(monkeypatch):
    from repro.api.shards import resolve_shard_count

    monkeypatch.setenv("REPRO_SHARD_TARGET_ROWS", "100")
    n, decision = resolve_shard_count({"shards": "auto"}, n_rows=350)
    assert n == 4 and decision["by_rows"] == 4
    assert decision["requested"] == "auto" and decision["chosen"] == 4

    n, decision = resolve_shard_count({"shards": 7}, n_rows=350)
    assert n == 7 and decision is None, "explicit counts bypass auto-tuning"


def test_resolve_shard_count_auto_caps_at_fleet_capacity(monkeypatch):
    from repro.api.shards import resolve_shard_count

    class FakeQueue:
        def runner_cards(self, live_only=True):
            return [{"capacity": 2}, {"capacity": 1}]

    monkeypatch.setenv("REPRO_SHARD_TARGET_ROWS", "10")
    n, decision = resolve_shard_count({"shards": "auto"}, n_rows=10_000,
                                      queue=FakeQueue())
    assert decision["live_capacity"] == 3
    assert n == decision["cap"] == 6, "auto shards cap at 2x live capacity"
