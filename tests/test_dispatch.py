"""Adaptive windowed dispatcher: speculative straggler re-dispatch on the
streaming chain path, failure retries (backup wins over a failed original),
per-call redispatch deltas, failing-op attribution, and worker quarantine."""
import concurrent.futures as cf
import os
import threading
import time

import pytest

from repro.core import dispatch as D
from repro.core.dataset import DJDataset
from repro.core.engine import LocalEngine, ParallelEngine
from repro.core.executor import Executor
from repro.core.recipes import Recipe
from repro.core.registry import create_op, register
from repro.core.ops_base import Mapper
from repro.core.storage import write_jsonl
from repro.data.synthetic import make_corpus


# ---------------------------------------------------------------------------
# injected fixtures (registered so forked worker processes can rebuild them)
# ---------------------------------------------------------------------------


@register("sleep_once_mapper")
class SleepOnceMapper(Mapper):
    """Sleeps ``delay`` on a marked sample the FIRST time its block is
    attempted (atomic flag-file claim) — a speculative backup runs fast."""

    _name = "sleep_once_mapper"

    def __init__(self, flag_dir: str, delay: float = 0.8, **kw):
        super().__init__(flag_dir=flag_dir, delay=delay, **kw)

    def process_single(self, s):
        key = s.get("meta", {}).get("straggle_key")
        if key:
            try:
                os.close(os.open(os.path.join(self.params["flag_dir"], key),
                                 os.O_CREAT | os.O_EXCL | os.O_WRONLY))
                time.sleep(self.params["delay"])
            except FileExistsError:
                pass
        s["text"] = s.get("text", "").strip()
        return s


@register("io_sleep_once_mapper")
class IOSleepOnceMapper(SleepOnceMapper):
    """io_intensive variant — routes LocalEngine onto its threaded window."""

    _name = "io_sleep_once_mapper"
    io_intensive = True


@register("slow_first_attempt_mapper")
class SlowFirstAttemptMapper(Mapper):
    """Sleeps ``delay`` per marked sample on the FIRST attempt of a block
    (atomic flag-file claim per straggle_key) — a speculative backup runs
    fast and wins. The slow (losing) attempt drops a ``drained-<key>``
    marker if it ever reaches the block's last sample: the preemption
    regression test asserts that marker never appears."""

    _name = "slow_first_attempt_mapper"
    io_intensive = True  # routes LocalEngine onto its threaded window

    def __init__(self, flag_dir: str, delay: float = 0.1, **kw):
        super().__init__(flag_dir=flag_dir, delay=delay, **kw)
        self._claims = {}

    def process_single(self, s):
        key = s.get("meta", {}).get("straggle_key")
        if key:
            claimed = self._claims.get(key)
            if claimed is None:
                try:
                    os.close(os.open(os.path.join(self.params["flag_dir"], key),
                                     os.O_CREAT | os.O_EXCL | os.O_WRONLY))
                    claimed = True
                except FileExistsError:
                    claimed = False
                self._claims[key] = claimed
            if claimed:
                time.sleep(self.params["delay"])
                if s.get("meta", {}).get("last_of_block"):
                    with open(os.path.join(self.params["flag_dir"],
                                           f"drained-{key}"), "w") as f:
                        f.write("loser drained to completion")
        s["text"] = s.get("text", "").strip()
        return s


@register("prefix_once_mapper")
class PrefixOnceMapper(Mapper):
    """NON-idempotent: applied twice, the marker doubles — catches a
    speculative backup sharing (and re-mutating) the original's dicts."""

    _name = "prefix_once_mapper"

    def process_single(self, s):
        s["text"] = "X" + s.get("text", "")
        return s


@register("fail_once_setup_op")
class FailOnceSetupMapper(Mapper):
    """Worker-level failure (escapes the per-sample exception manager) on the
    first dispatch only — the retry/backup must win, not pass-through."""

    _name = "fail_once_setup_op"

    def __init__(self, flag_dir: str, **kw):
        super().__init__(flag_dir=flag_dir, **kw)

    def setup(self):
        try:
            os.close(os.open(os.path.join(self.params["flag_dir"], "failed"),
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY))
        except FileExistsError:
            return
        raise RuntimeError("injected one-time worker failure")

    def process_single(self, s):
        s["text"] = s.get("text", "").upper()
        return s


@register("always_fail_setup_op")
class AlwaysFailSetupMapper(Mapper):
    _name = "always_fail_setup_op"

    def setup(self):
        raise RuntimeError("permanently broken op")

    def process_single(self, s):  # pragma: no cover — setup always raises
        return s


def _marked_blocks(n_samples=160, n_blocks=8, slow=(3,)):
    corpus = make_corpus(n_samples, seed=17)
    blocks = DJDataset.from_samples([dict(s) for s in corpus],
                                    n_blocks_hint=n_blocks).blocks
    for b in slow:
        s = dict(blocks[b].samples[0])
        s["meta"] = dict(s.get("meta", {}), straggle_key=f"blk{b}")
        blocks[b].samples[0] = s
    return blocks


# ---------------------------------------------------------------------------
# speculation on the streaming chain path
# ---------------------------------------------------------------------------


def test_chain_speculation_fires_and_output_identical(tmp_path):
    cfgs = [{"name": "sleep_once_mapper", "flag_dir": str(tmp_path), "delay": 0.8},
            {"name": "whitespace_normalization_mapper"}]
    blocks = _marked_blocks()
    # ref on UNMARKED blocks (same text output, no flag claims, no sleeping):
    # the parallel run below must see virgin flag files so originals stall
    ref = [s["text"]
           for blk, _ in LocalEngine().map_block_chain(
               [create_op(c) for c in cfgs], iter(_marked_blocks(slow=())))
           for s in blk.samples]

    eng = ParallelEngine(n_workers=2, straggler_factor=2.0, min_completions=2)
    got = [s["text"]
           for blk, _ in eng.map_block_chain([create_op(c) for c in cfgs],
                                             iter(blocks))
           for s in blk.samples]
    assert got == ref, "speculation must keep outputs byte-identical, in order"
    summary = eng.dispatch_log[-1]
    assert summary["redispatches"] >= 1, f"speculation never fired: {summary}"
    assert summary["speculation_wins"] >= 1
    assert summary["pass_throughs"] == 0
    assert eng.redispatches >= 1  # cumulative counter still maintained


def test_local_threaded_speculation_no_shared_mutation(tmp_path):
    """Thread pools share objects: a speculative backup must process its own
    copy, never re-mutating dicts the straggling original still writes."""
    cfgs = [{"name": "io_sleep_once_mapper", "flag_dir": str(tmp_path), "delay": 0.6},
            {"name": "prefix_once_mapper"}]
    ref = [s["text"]
           for blk, _ in LocalEngine().map_block_chain(
               [create_op(c) for c in cfgs], iter(_marked_blocks(slow=())))
           for s in blk.samples]
    eng = LocalEngine(n_threads=2, straggler_factor=2.0, speculate=True)
    got = [s["text"]
           for blk, _ in eng.map_block_chain([create_op(c) for c in cfgs],
                                             iter(_marked_blocks()))
           for s in blk.samples]
    assert got == ref, "threaded speculation must not double-apply mutations"
    assert all(not t.startswith("XX") for t in got)
    assert eng.dispatch_log[-1]["engine"] == "local"


def test_speculation_disabled_never_redispatches(tmp_path):
    cfgs = [{"name": "sleep_once_mapper", "flag_dir": str(tmp_path), "delay": 0.2}]
    eng = ParallelEngine(n_workers=2, speculate=False, min_completions=2,
                         straggler_factor=2.0)
    list(eng.map_block_chain([create_op(c) for c in cfgs],
                             iter(_marked_blocks())))
    assert eng.dispatch_log[-1]["redispatches"] == 0


# ---------------------------------------------------------------------------
# failure handling: retry/backup wins; pass-through only when ALL failed
# ---------------------------------------------------------------------------


def test_failed_dispatch_retries_instead_of_pass_through(tmp_path):
    op = create_op({"name": "fail_once_setup_op", "flag_dir": str(tmp_path)})
    blocks = DJDataset.from_samples(make_corpus(80, seed=5), n_blocks_hint=4).blocks
    eng = ParallelEngine(n_workers=2, speculate=False)
    out, stats = eng.map_batches(op, blocks, 64)
    texts = [s["text"] for b in out for s in b.samples]
    assert texts and all(t == t.upper() for t in texts), \
        "retried block must carry the op's REAL output, not the input pass-through"
    assert not op.errors, "a won retry is not a block failure"
    assert eng.dispatch_log[-1]["retries"] >= 1
    assert stats["redispatches"] == 0  # retries are not speculation


def test_pass_through_only_after_all_attempts_fail():
    op = create_op({"name": "always_fail_setup_op"})
    corpus = make_corpus(60, seed=9)
    blocks = DJDataset.from_samples([dict(s) for s in corpus], n_blocks_hint=3).blocks
    eng = ParallelEngine(n_workers=2, speculate=False)
    out, _ = eng.map_batches(op, blocks, 64)
    assert [s["text"] for b in out for s in b.samples] == \
           [s["text"] for s in corpus], "exhausted block passes input through"
    assert len(op.errors) == len(blocks)
    assert all("attempts" in e.error for e in op.errors)
    assert eng.dispatch_log[-1]["pass_throughs"] == len(blocks)


# ---------------------------------------------------------------------------
# per-call EngineStats delta (was: cumulative count inflating later runs)
# ---------------------------------------------------------------------------


def test_engine_stats_reports_per_call_redispatch_delta():
    eng = ParallelEngine(n_workers=2)
    eng.redispatches = 7  # as if earlier calls speculated
    op = create_op({"name": "whitespace_normalization_mapper"})
    blocks = DJDataset.from_samples(make_corpus(40, seed=2), n_blocks_hint=2).blocks
    _, stats = eng.map_batches(op, blocks, 64)
    assert stats["redispatches"] == 0, "EngineStats must report THIS call's count"
    assert eng.redispatches == 7, "cumulative counter untouched by a clean call"


# ---------------------------------------------------------------------------
# chain failures attribute the failing op (was: always pinned to ops[0])
# ---------------------------------------------------------------------------


def test_chain_failure_attributed_to_failing_op():
    cfgs = [{"name": "whitespace_normalization_mapper"},
            {"name": "always_fail_setup_op"}]
    ops = [create_op(c) for c in cfgs]
    corpus = make_corpus(40, seed=11)
    blocks = DJDataset.from_samples([dict(s) for s in corpus], n_blocks_hint=2).blocks
    eng = ParallelEngine(n_workers=2, speculate=False)
    out = list(eng.map_block_chain(ops, iter(blocks)))
    assert not ops[0].errors, "healthy op must not absorb the failure"
    assert len(ops[1].errors) == len(blocks)
    assert all("permanently broken op" in e.error for e in ops[1].errors)
    for _, stats in out:
        assert [st["errors"] for st in stats] == [0, 1], \
            "synthesized stats must pin the error to the failing op's row"
    # pass-through keeps the samples flowing
    assert sum(len(b) for b, _ in out) == len(corpus)


# ---------------------------------------------------------------------------
# worker quarantine
# ---------------------------------------------------------------------------


def test_quarantined_worker_stops_receiving_blocks():
    lock = threading.Lock()
    executed = []
    state = {"bad": None}

    def fn(item):
        wid = D._worker_id()
        with lock:
            if state["bad"] is None:
                state["bad"] = wid  # first thread to run a task goes bad
            executed.append((wid, item))
        if wid == state["bad"]:
            raise RuntimeError("wedged worker")
        time.sleep(0.005)
        return item * 2

    log = []
    with cf.ThreadPoolExecutor(2) as pool:
        disp = D.WindowedDispatcher(
            pool, 2, speculate=False, max_attempts=8, worker_failure_limit=2,
            bounce_limit=100, label="quarantine", log=log)
        results = list(disp.run(range(40), fn, lambda x: (x,)))

    assert [p for _, p, _ in results] == [x * 2 for x in range(40)]
    assert all(e is None for _, _, e in results)
    summary = log[-1]
    assert summary["quarantined"] == [state["bad"]]
    bad_execs = [i for w, i in executed if w == state["bad"]]
    # pre-quarantine in-flight submissions may still land on the bad worker;
    # once quarantined it only bounces (payload never executes there again)
    assert len(bad_execs) <= 8, f"quarantined worker kept executing: {bad_execs}"
    assert summary["bounces"] >= 1


def test_window_stays_within_bounds():
    log = []
    with cf.ThreadPoolExecutor(2) as pool:
        disp = D.WindowedDispatcher(pool, 2, speculate=False, label="w", log=log)
        results = list(disp.run(range(64), lambda x: x, lambda x: (x,)))
    assert [p for _, p, _ in results] == list(range(64))
    s = log[-1]
    assert disp.min_window <= s["window_final"] <= disp.max_window
    assert s["blocks"] == 64


# ---------------------------------------------------------------------------
# preemptive loser cancellation (ROADMAP leak: a sleeper used to occupy its
# worker until it drained the whole chain)
# ---------------------------------------------------------------------------


def test_losing_original_is_preempted_not_drained(tmp_path):
    """When a speculative backup wins, the straggling original must be
    preemptively cancelled (exit at its next batch boundary), not left
    draining on its worker: the drain marker must never appear, the summary
    must record the preempt signal, and wall-clock must beat the drain."""
    corpus = make_corpus(48, seed=23)
    blocks = DJDataset.from_samples([dict(s) for s in corpus],
                                    n_blocks_hint=6).blocks
    # block 1: every sample marked -> 24 batches x 0.12s of first-attempt
    # sleeping; the final sample drops the drain marker if ever reached
    straggler = [dict(s, meta={"straggle_key": "blk1"})
                 for s in blocks[1].samples for _ in range(3)]
    straggler[-1]["meta"] = dict(straggler[-1]["meta"], last_of_block=True)
    from repro.core.storage import SampleBlock
    blocks[1] = SampleBlock(straggler)
    total = sum(len(b.samples) for b in blocks)

    cfgs = [{"name": "slow_first_attempt_mapper", "flag_dir": str(tmp_path),
             "delay": 0.12}]
    drain_seconds = len(straggler) * 0.12  # what a drained loser would cost

    eng = LocalEngine(n_threads=2, straggler_factor=2.0, speculate=True)
    t0 = time.time()
    out = list(eng.map_block_chain([create_op(c) for c in cfgs],
                                   iter(blocks), batch_size=2))
    elapsed = time.time() - t0

    assert sum(len(b.samples) for b, _ in out) == total
    summary = eng.dispatch_log[-1]
    assert summary["speculation_wins"] >= 1, f"backup never won: {summary}"
    assert summary["preempt_signals"] >= 1, \
        f"winning backup must signal the running loser: {summary}"
    assert not os.path.exists(str(tmp_path / "drained-blk1")), \
        "the losing original drained its block instead of being preempted"
    # the engine's pool shutdown waits for the loser, so a drained loser
    # would push elapsed past drain_seconds; a preempted one exits within
    # about one batch (2 x 0.12s)
    assert elapsed < drain_seconds * 0.7, \
        f"run took {elapsed:.2f}s — the loser occupied its worker to the end"


def test_preempted_losers_are_counted():
    """Direct dispatcher check: a cooperative fn that honours should_stop is
    counted under summary['preempted'] (observed early exits)."""
    attempts = {"slow": 0}
    lock = threading.Lock()

    def fn(item, should_stop):
        if item == "slow":
            with lock:
                attempts["slow"] += 1
                first = attempts["slow"] == 1
            if first:  # the original spins until preempted; the backup is fast
                while not should_stop():
                    time.sleep(0.005)
                raise D.TaskPreempted("observed the board")
            return item
        time.sleep(0.02)  # keep the stream alive past the loser's exit
        return item

    log = []
    with cf.ThreadPoolExecutor(2) as pool:
        disp = D.WindowedDispatcher(pool, 2, straggler_factor=2.0,
                                    min_completions=2, label="preempt",
                                    log=log, preempt_board={})
        items = ["a", "b", "slow", "c", "d", "e", "f", "g"]
        results = list(disp.run(items, fn, lambda x: (x,)))
    got = [p for _, p, _ in results]
    assert got == items, "the winning backup must supply the payload"
    assert log[-1]["preempt_signals"] == 1
    assert log[-1]["preempted"] == 1
    assert log[-1]["speculation_wins"] == 1


# ---------------------------------------------------------------------------
# cross-run worker-health persistence (HealthRegistry)
# ---------------------------------------------------------------------------


def _run_dispatch(health, fail_first_n=0, n_workers=2, limit=2, items=24):
    """One dispatcher 'run' over a single REAL worker thread (slot identity
    is then deterministic: the only wid ever seen maps to slot w0) with the
    first ``fail_first_n`` executions failing."""
    calls = {"n": 0}

    def fn(item):
        calls["n"] += 1
        if calls["n"] <= fail_first_n:
            raise RuntimeError("injected worker failure")
        return item

    log = []
    with cf.ThreadPoolExecutor(1) as pool:
        disp = D.WindowedDispatcher(
            pool, n_workers, speculate=False, max_attempts=10,
            worker_failure_limit=limit, bounce_limit=3, bounce_pause=0.0,
            label="health", log=log, health=health)
        results = list(disp.run(range(items), fn, lambda x: (x,)))
    assert all(e is None for _, _, e in results)
    return log[-1]


def test_quarantine_persists_and_probation_limits_next_run(tmp_path):
    """ROADMAP item: quarantine was in-run only. A worker slot quarantined in
    run 1 must start run 2 on probation (one strike re-quarantines), and a
    clean probation run must clear it again."""
    path = str(tmp_path / "health.json")

    # run 1: two failures hit the default limit -> quarantined, persisted
    summary = _run_dispatch(D.HealthRegistry(path), fail_first_n=2, limit=2)
    assert summary["quarantined"], "run 1 must quarantine the bad worker"
    reloaded = D.HealthRegistry(path)
    assert reloaded.on_probation("w0"), \
        "quarantine must survive into the next run as probation"
    assert reloaded.total_quarantines() == 1

    # run 2: probation drops the allowance to ONE strike (limit is 3 here)
    disp = D.WindowedDispatcher(None, 2,
                                worker_failure_limit=3, health=reloaded)
    assert disp._failure_limit("some-wid") == 1, \
        "probation slot must be one-strike"
    summary2 = _run_dispatch(reloaded, fail_first_n=1, limit=3)
    assert summary2["quarantined"], \
        "a single failure must re-quarantine a probation worker"
    assert D.HealthRegistry(path).on_probation("w0")

    # run 3: a clean run recovers the slot — full allowance next time
    _run_dispatch(D.HealthRegistry(path), fail_first_n=0)
    final = D.HealthRegistry(path)
    assert not final.on_probation("w0")
    assert final.slots["w0"]["recoveries"] >= 1
    disp3 = D.WindowedDispatcher(None, 2,
                                 worker_failure_limit=3, health=final)
    assert disp3._failure_limit("any-wid") == 3


def test_health_registry_roundtrip_property(tmp_path):
    """Hypothesis property: the health file round-trips through ARBITRARY
    quarantine/failure/recovery/forgive sequences — reload always equals the
    in-memory state, and probation is exactly 'quarantined since the last
    recovery/forgive'."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    ops = st.lists(st.tuples(
        st.sampled_from(["failure", "quarantine", "recovery", "forgive"]),
        st.sampled_from(["w0", "w1", "w2"])), max_size=60)

    @settings(max_examples=60, deadline=None)
    @given(seq=ops)
    def check(seq):
        path = str(tmp_path / "h.json")
        if os.path.exists(path):
            os.remove(path)
        reg = D.HealthRegistry(path)
        expected_probation = {}
        for op, key in seq:
            getattr(reg, f"note_{op}" if op != "forgive" else "forgive")(key)
            if op == "quarantine":
                expected_probation[key] = True
            elif op in ("recovery", "forgive"):
                expected_probation[key] = False
        reg.save()
        back = D.HealthRegistry(path)
        assert back.snapshot() == reg.snapshot()
        for key, prob in expected_probation.items():
            assert back.on_probation(key) == prob
        assert back.total_quarantines() == reg.total_quarantines()

    check()


def test_corrupt_health_file_starts_fresh(tmp_path):
    path = str(tmp_path / "health.json")
    with open(path, "w") as f:
        f.write("{torn mid-write")
    reg = D.HealthRegistry(path)
    assert reg.slots == {}
    reg.note_quarantine("w0")
    reg.save()
    assert D.HealthRegistry(path).on_probation("w0")


def test_recipe_health_path_reaches_engine(tmp_path):
    """Recipe.health_path plumbs through the Executor into the engine (and
    is settable from the fluent API like any other option)."""
    from repro.api import Pipeline

    path = str(tmp_path / "health.json")
    src = str(tmp_path / "in.jsonl")
    write_jsonl(src, make_corpus(30, seed=3))
    pipe = (Pipeline.read_jsonl(src)
            .map("whitespace_normalization_mapper")
            .options(health_path=path, engine="parallel", np=2))
    eng = Executor(pipe.to_recipe())._make_engine()
    assert eng.health is not None and eng.health.path == path
    # pre-seeded probation is visible to the engine's dispatchers
    reg = D.HealthRegistry(path)
    reg.note_quarantine("w0")
    reg.save()
    eng2 = Executor(pipe.to_recipe())._make_engine()
    assert eng2.health.on_probation("w0")


# ---------------------------------------------------------------------------
# executor / report surfacing
# ---------------------------------------------------------------------------


def test_run_report_surfaces_dispatch_and_monitor_rows(tmp_path):
    src = str(tmp_path / "in.jsonl")
    write_jsonl(src, make_corpus(120, seed=21))
    r = Recipe(name="d", dataset_path=src, engine="parallel", np=2,
               process=[{"name": "whitespace_normalization_mapper"},
                        {"name": "text_length_filter", "min_val": 10}],
               block_bytes=4096)
    _, rep = Executor(r).run()
    assert rep.streaming
    assert rep.dispatch, "RunReport.dispatch must carry per-segment summaries"
    assert rep.dispatch[0]["label"] == "+".join(rep.plan)
    for key in ("redispatches", "quarantined", "window_final"):
        assert key in rep.dispatch[0]
    assert all("redispatches" in row for row in rep.per_op)
    # explain() documents the adaptive-dispatch policy without running
    ex = Executor(r).explain()
    assert ex["dispatch"]["speculation"] is True
    assert ex["dispatch"]["window"]["adaptive"] is True


# ---------------------------------------------------------------------------
# device ownership: model ops run in the driver, never in forked workers
# ---------------------------------------------------------------------------


@register("pid_stamp_mapper")
class PidStampMapper(Mapper):
    """Stamps the pid of the process that ran it into the sample's stats."""

    _name = "pid_stamp_mapper"

    def process_single(self, s):
        s.setdefault("stats", {})["pid"] = os.getpid()
        return s


@register("pid_stamp_model_mapper")
class PidStampModelMapper(PidStampMapper):
    _name = "pid_stamp_model_mapper"
    uses_model = True


@pytest.mark.parametrize("op_name,in_driver", [
    ("pid_stamp_model_mapper", True),
    ("pid_stamp_mapper", False),  # control: plain ops do reach the workers
])
@pytest.mark.parametrize("path", ["streaming", "barriered"])
def test_model_op_runs_in_driver_under_parallel_engine(tmp_path, op_name,
                                                       in_driver, path):
    src = str(tmp_path / "in.jsonl")
    write_jsonl(src, make_corpus(120, seed=5))
    r = Recipe(name="pid", dataset_path=src, engine="parallel", np=2,
               block_bytes=4096, use_fusion=False, use_reordering=False,
               process=[{"name": "whitespace_normalization_mapper"},
                        {"name": op_name}])
    ex = Executor(r)
    ds, rep = ex.run_streaming() if path == "streaming" else ex.run_barriered()
    pids = {s["stats"]["pid"] for s in ds.samples()}
    assert rep.errors == 0 and len(ds) == 120
    assert (pids == {os.getpid()}) is in_driver, pids


def test_fused_op_with_model_op_is_a_model_op():
    from repro.core.ops_base import FusedOP

    plain = create_op({"name": "text_length_filter"})
    model = create_op({"name": "lm_perplexity_filter"})
    assert not FusedOP([plain]).uses_model
    assert FusedOP([plain, model]).uses_model
