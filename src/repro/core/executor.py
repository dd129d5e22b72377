"""End-to-end workflow orchestration (paper §5.3).

Executor: config -> load -> validate -> probe -> fuse/reorder ->
process (fault-tolerant, checkpointed, monitored) -> insight -> export.

Two execution paths through the runtime layer:

  * barriered — one dataset-wide pass per OP with full materialization
    between OPs. Required for per-OP insight mining and per-OP checkpoints.
  * streaming — the OP plan is partitioned into pipelineable segments
    (chains of batch-level Mappers/Filters) separated by barrier OPs
    (Selector / Grouper / Aggregator — and Deduplicator unless it opted into
    the incremental streaming protocol, which runs as a stateful stream
    STAGE instead); each block traverses a whole segment in ONE worker
    dispatch, fed by a bounded prefetch queue from the streaming JSONL
    reader and exported block-by-block, so the full dataset is only
    materialized at genuine barriers (paper §E.3, Fig. 4f). Insight mining
    rides the stream too (one snapshot per segment, SegmentInsightRecorder),
    and the optimizer probe is a uniform reservoir over the first scan
    window of the live block stream.

``run()`` selects the streaming path automatically unless the recipe
checkpoints (operator-level checkpoints persist whole stages);
``run_streaming()`` forces it (checkpointing then happens at segment
boundaries instead of per-op).
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from repro.core import clock, obs
from repro.core.adapter import Adapter
from repro.core.checkpoint import CheckpointManager, recipe_prefix_sigs
from repro.core.dataset import (
    DJDataset, ExecutionCancelled, iter_stream_blocks, seed_op_entries,
    seed_plan_entries, stream_segments,
)
from repro.core.engine import make_engine
from repro.core.fusion import plan_segments
from repro.core.insight import InsightMiner, SegmentInsightRecorder
from repro.core.ops_base import Operator
from repro.core.plan import LogicalPlan
from repro.core.recipes import Recipe
from repro.core.rules import annotate_plan, optimize_plan
from repro.core.registry import create_op
from repro.core.storage import (
    BlockPrefetcher, BlockWriter, SampleBlock, iter_sample_blocks,
    read_jsonl, reservoir_sample, split_blocks,
)

PROBE_LIMIT = 1000
# streaming probe: uniform reservoir over the first PROBE_SCAN_FACTOR x
# PROBE_LIMIT rows of the block stream (vs. the old head-biased first-1000)
PROBE_SCAN_FACTOR = 8
# explain() is a dry-run surface: probe far fewer samples than a real run
# so the command stays cheap even with slow/model-backed ops in the plan
EXPLAIN_PROBE_LIMIT = 128


@dataclasses.dataclass
class RunReport:
    recipe: str
    n_in: int
    n_out: int
    seconds: float
    per_op: List[dict]
    plan: List[str]
    resumed_at: int = 0
    insight: str = ""
    errors: int = 0
    streaming: bool = False
    # one summary per engine dispatch call (label = segment's op chain):
    # redispatches, speculation_wins, retries, quarantined workers, window
    dispatch: List[dict] = dataclasses.field(default_factory=list)
    # merged trace for this run: {"trace_id", "root_span", "spans": [...]}
    # (core.obs span dicts — run -> dispatch windows -> worker block
    # spans). None when tracing is disabled.
    trace: Optional[Dict[str, Any]] = None


def _count_blocks(blocks: Iterable[SampleBlock], counter: Dict[str, int]) -> Iterator[SampleBlock]:
    for b in blocks:
        counter["n"] += len(b)
        yield b


class Executor:
    def __init__(self, recipe: Recipe, adapter: Optional[Adapter] = None):
        self.recipe = recipe
        self.adapter = adapter or Adapter()
        # set by _optimize_ops: the optimized LogicalPlan and the per-rule
        # rewrite diffs of the last optimization (explain / plan pinning /
        # the plan:optimize trace span all read these)
        self.last_plan: Optional[LogicalPlan] = None
        self.last_rewrites: List[dict] = []

    def _build_ops(self) -> List[Operator]:
        return [create_op(cfg) for cfg in self.recipe.process]

    def _plan_ops(self) -> Tuple[List[Operator], bool]:
        """(ops, fixed): a persisted ``fixed_plan`` (cluster failover replay)
        is rebuilt verbatim — the caller must then skip probe + optimize."""
        r = self.recipe
        if r.fixed_plan is not None:
            return [create_op(dict(c)) for c in r.fixed_plan], True
        return self._build_ops(), False

    def resolve_plan(self) -> List[Dict[str, Any]]:
        """Derive the optimized op plan WITHOUT running the recipe: the same
        probe + optimize a streaming run would perform, returned as op
        configs. Cluster runners persist this at first claim so a failover
        retry re-runs the identical plan (``Recipe.fixed_plan``)."""
        r = self.recipe
        if r.fixed_plan is not None:
            return [dict(c) for c in r.fixed_plan]
        ops = self._build_ops()
        if (r.use_fusion or r.use_reordering) and r.dataset_path:
            bb = {"block_bytes": r.block_bytes} if r.block_bytes else {}
            raw = iter_sample_blocks(r.dataset_path, n_workers=1, **bb)
            try:
                probe, _ = self._probe_blocks(raw)
            finally:
                raw.close()
            ops = self._optimize_ops(ops, probe)
        return [op.config() for op in ops]

    def _columnar_source(self) -> bool:
        return self.recipe.block_format != "row"

    def _make_engine(self):
        r = self.recipe
        kw: Dict[str, Any] = {}
        if r.engine == "parallel":
            kw["n_workers"] = r.np
        if r.health_path and r.engine in ("local", "parallel"):
            kw["health_path"] = r.health_path
        if r.engine in ("local", "parallel"):
            mb = r.mem_budget
            if mb is None:
                try:
                    mb = int(os.environ.get("DJ_BLOCK_MEM_BUDGET", "") or 0) or None
                except ValueError:
                    mb = None
            if mb:
                kw["mem_budget"] = mb
        return make_engine(r.engine, **kw)

    def streaming_eligible(self) -> bool:
        """Streaming drops the per-op dataset-wide barrier. Insight mining
        rides the block stream now (SegmentInsightRecorder: one timeline
        entry per segment instead of per op), so only operator-level
        checkpointing — which must persist whole stages — keeps the
        barriered path on auto-selection."""
        return not self.recipe.checkpoint_dir

    def run(self, dataset: Optional[DJDataset] = None,
            monitor: Optional[List[dict]] = None,
            cancel=None) -> tuple[DJDataset, RunReport]:
        """Execute the recipe. ``monitor`` (a caller-owned list) receives the
        live per-op progress rows; ``cancel`` is a callable polled during the
        run — returning True aborts with ExecutionCancelled. Both power the
        async job subsystem (repro.api.jobs)."""
        if self.streaming_eligible():
            return self.run_streaming(dataset, monitor=monitor, cancel=cancel)
        return self.run_barriered(dataset, monitor=monitor, cancel=cancel)

    # ------------------------------------------------------------------
    # tracing (core.obs): every run executes under a "run" span. The trace
    # id is inherited from recipe.trace (cluster submit / shard task) or
    # minted here for local runs; the span is pushed as the thread's ambient
    # parent so engine dispatch windows (and their worker block spans)
    # attach to it without any signature changes down the stack.
    # ------------------------------------------------------------------
    def _begin_run_span(self, path: str):
        tr = self.recipe.trace or {}
        trace_id = tr.get("trace_id") or (obs.new_id() if obs.enabled() else None)
        sp = obs.start_span(trace_id, f"run:{self.recipe.name}", kind="run",
                            parent_id=tr.get("span_id"))
        if sp is not None:
            sp.set(path=path, engine=self.recipe.engine, np=self.recipe.np)
            obs.tracer().stack().append(sp)
        return sp

    def _pop_run_span(self, sp) -> None:
        if sp is None:
            return
        stack = obs.tracer().stack()
        if sp in stack:
            stack.remove(sp)

    def _finish_run_span(self, sp, report: RunReport) -> None:
        """End the run span and attach the drained trace to the report.
        Ops get no spans: their per-block work carries ``op:<name>``
        profiler annotations, and their seconds are the report's per-op
        rows."""
        if sp is None:
            return
        sp.set(n_in=report.n_in, n_out=report.n_out, errors=report.errors,
               streaming=report.streaming, resumed_at=report.resumed_at)
        sp.end()
        obs.metrics().inc("run.rows_out_total", report.n_out)
        report.trace = {"trace_id": sp.trace_id, "root_span": sp.span_id,
                        "spans": obs.drain(sp.trace_id)}

    # ------------------------------------------------------------------
    # streaming block-pipelined path
    # ------------------------------------------------------------------
    def _optimize_ops(self, ops: List[Operator], probe_samples: List[dict]) -> List[Operator]:
        """Probe + rule-based optimization over the logical-plan IR. The
        optimized plan and the per-rule rewrite diffs are kept on the
        executor (``last_plan`` / ``last_rewrites``) and emitted as a
        ``plan:optimize`` span under the ambient run span."""
        r = self.recipe
        if (r.use_fusion or r.use_reordering) and probe_samples:
            t0 = clock.now()
            self.adapter.probe_small_batch(probe_samples, ops)
            plan, rewrites = optimize_plan(
                LogicalPlan.from_ops(ops), self.adapter.probes,
                do_fuse=r.use_fusion, do_reorder=r.use_reordering)
            self.last_plan = plan
            self.last_rewrites = [rw.to_dict() for rw in rewrites]
            self._emit_plan_span(t0, self.last_rewrites)
            ops = plan.ops()
        return ops

    def _emit_plan_span(self, t0: float, rewrites: List[dict]) -> None:
        """Log the optimizer's per-rule before/after diffs onto the trace
        (kind="plan"), parented under the ambient run span when one is
        active, else under the recipe's submitted trace context."""
        if not obs.enabled():
            return
        tr = self.recipe.trace or {}
        stack = obs.tracer().stack()
        parent = stack[-1] if stack else None
        trace_id = parent.trace_id if parent is not None else tr.get("trace_id")
        sp = obs.start_span(
            trace_id, "plan:optimize", kind="plan",
            parent_id=parent.span_id if parent is not None else tr.get("span_id"),
            t0=t0)
        if sp is not None:
            sp.set(rules=rewrites,
                   n_rules_changed=sum(1 for rw in rewrites if rw["changed"]))
            sp.end()

    def _probe_samples(self, dataset: Optional[DJDataset]) -> List[dict]:
        if dataset is not None:
            # in-memory: hand the adapter the full pool — probe_small_batch
            # picks its own random subset, matching the barriered path
            return dataset.samples()
        if self.recipe.dataset_path:
            return list(read_jsonl(self.recipe.dataset_path, limit=PROBE_LIMIT))
        return []

    def _probe_blocks(self, src: Iterable[SampleBlock]
                      ) -> Tuple[List[dict], Iterable[SampleBlock]]:
        """Reservoir-sampled probe over the first block pass.

        Replaces the head-biased ``read_jsonl(limit=1000)`` probe for
        streamed file sources: scan blocks off the live stream until the
        reservoir window has seen PROBE_SCAN_FACTOR x PROBE_LIMIT rows (or
        the stream ends), draw a uniform PROBE_LIMIT-row sample from that
        window, and plan right then — the replan happens exactly once, when
        the reservoir fills. The scanned blocks are replayed ahead of the
        remaining stream, so nothing is decoded twice and resident memory
        stays O(scan window). Deterministic (fixed seed + first-seen order),
        so checkpoint resume re-derives the identical optimized plan."""
        scanned: List[SampleBlock] = []
        seen = 0
        for blk in src:
            scanned.append(blk)
            seen += len(blk)
            if seen >= PROBE_LIMIT * PROBE_SCAN_FACTOR:
                break
        # private decode for ColumnBlocks: .samples would cache row dicts and
        # mark the whole scan window materialized (losing its columnar path)
        probe = reservoir_sample(
            (s for b in scanned
             for s in (b.decode_rows() if hasattr(b, "decode_rows") else b.samples)),
            PROBE_LIMIT)
        return probe, itertools.chain(scanned, src)

    def explain(self, dataset: Optional[DJDataset] = None) -> Dict[str, Any]:
        """Optimized plan + streaming segments WITHOUT processing the
        dataset. Fusion/reordering need probed op speeds, so each op runs on
        a small head sample (EXPLAIN_PROBE_LIMIT rows — much smaller than a
        real run's probe, so the reordering can differ marginally); with no
        data source available, optimization falls back to declaration order."""
        r = self.recipe
        ops = self._optimize_ops(
            self._build_ops(), self._probe_samples(dataset)[:EXPLAIN_PROBE_LIMIT])
        segments = plan_segments(ops)
        src = {"kind": "jsonl", "path": r.dataset_path} if r.dataset_path else None
        opts = {"export_path": r.export_path} if r.export_path else {}
        plan_ir = self.last_plan or annotate_plan(LogicalPlan.from_ops(ops))
        plan_ir = LogicalPlan(src, plan_ir.nodes, opts)
        return {
            "recipe": r.name,
            "requested": [cfg.get("name") for cfg in r.process],
            "plan": [op.name for op in ops],
            # the optimized logical plan: typed Source/.../Sink nodes with
            # column deps + rule annotations, and the per-rule rewrite diffs
            "nodes": plan_ir.describe(),
            "rewrites": list(self.last_rewrites),
            "segments": [
                {"ops": [o.name for o in seg.ops], "barrier": seg.barrier,
                 "stateful": seg.stateful, "pushdown": seg.n_pushdown}
                for seg in segments
            ],
            "streaming": self.streaming_eligible(),
            "engine": r.engine,
            "np": r.np,
            # adaptive-dispatch policy the run will use (window sizing,
            # speculation, quarantine — docs/runtime.md "Adaptive dispatch")
            "dispatch": self._make_engine().dispatch_policy(),
            # whether the run will record a trace (docs/observability.md)
            "obs": {"tracing": obs.enabled()},
        }

    def stream_blocks(
        self, dataset: Optional[DJDataset] = None, prefetch: int = 4,
        monitor: Optional[List[dict]] = None, cancel=None,
    ) -> Iterator[Any]:
        """Lazy generator over output SampleBlocks: probe -> optimize ->
        stream, with no export and no full materialization (except at
        barrier ops). Powers ``Pipeline.iter_blocks``."""
        r = self.recipe
        if dataset is None and not r.dataset_path:
            raise ValueError("recipe has no dataset_path and no dataset given")
        engine = self._make_engine()
        ops, fixed = self._plan_ops()
        n_workers = getattr(engine, "n_workers", 1) or 1
        if dataset is not None:
            src: Iterable[SampleBlock] = iter(dataset.blocks)
            if not fixed:
                ops = self._optimize_ops(ops, self._probe_samples(dataset))
        else:
            bb = {"block_bytes": r.block_bytes} if r.block_bytes else {}
            if r.row_range:  # shard task: read only this slice of the input
                bb["row_range"] = tuple(r.row_range)
            src = iter_sample_blocks(r.dataset_path, n_workers=n_workers,
                                     columnar=self._columnar_source(), **bb)
            if (r.use_fusion or r.use_reordering) and not fixed:
                probe, src = self._probe_blocks(src)
                ops = self._optimize_ops(ops, probe)
        segments = plan_segments(ops)
        entries = seed_plan_entries(segments)
        if monitor is not None:
            monitor.extend(entries)
        prefetcher: Optional[BlockPrefetcher] = None
        if prefetch and dataset is None:
            src = prefetcher = BlockPrefetcher(src, depth=prefetch)
        try:
            yield from iter_stream_blocks(src, segments, engine, entries,
                                          n_workers, cancel)
        finally:
            if prefetcher is not None:
                prefetcher.close()

    def run_streaming(
        self, dataset: Optional[DJDataset] = None,
        materialize: bool = True, prefetch: int = 4,
        monitor: Optional[List[dict]] = None, cancel=None,
    ) -> tuple[DJDataset, RunReport]:
        """Streaming block-pipelined execution. With ``materialize=False``
        (and an ``export_path``) the output dataset is streamed to disk and
        the returned DJDataset is empty. A ``checkpoint_dir`` still forces
        per-segment materialization (stages are persisted whole), so peak
        memory is then one full dataset even with ``materialize=False``."""
        sp = self._begin_run_span("streaming")
        try:
            ds, report = self._run_streaming_impl(
                dataset, materialize=materialize, prefetch=prefetch,
                monitor=monitor, cancel=cancel)
        finally:
            self._pop_run_span(sp)  # never leak a stale ambient parent
        self._finish_run_span(sp, report)
        return ds, report

    def _run_streaming_impl(
        self, dataset: Optional[DJDataset] = None,
        materialize: bool = True, prefetch: int = 4,
        monitor: Optional[List[dict]] = None, cancel=None,
    ) -> tuple[DJDataset, RunReport]:
        r = self.recipe
        t0 = clock.now()
        engine = self._make_engine()
        if dataset is None and not r.dataset_path:
            raise ValueError("recipe has no dataset_path and no dataset given")

        ops, fixed = self._plan_ops()
        n_workers = getattr(engine, "n_workers", 1) or 1

        # source FIRST: with a file source the probe rides the live block
        # stream (uniform reservoir over the first scan window, replayed
        # ahead of the remaining stream) instead of a separate head-biased
        # read_jsonl(limit=...) pass
        counter = {"n": 0}
        counted = None
        if dataset is not None:
            counter["n"] = len(dataset)
            src: Iterable[SampleBlock] = iter(dataset.blocks)
            if not fixed:
                ops = self._optimize_ops(ops, self._probe_samples(dataset))
        else:
            bb = {"block_bytes": r.block_bytes} if r.block_bytes else {}
            if r.row_range:  # shard task: read only this slice of the input
                bb["row_range"] = tuple(r.row_range)
            counted = _count_blocks(
                iter_sample_blocks(r.dataset_path, n_workers=n_workers,
                                   columnar=self._columnar_source(), **bb), counter)
            src = counted
            if (r.use_fusion or r.use_reordering) and not fixed:
                # NOTE: on a checkpoint resume this scan is still required —
                # the resume point is keyed by the OPTIMIZED plan's prefix
                # sigs, and only the identical (deterministic) probe
                # re-derives the identical plan (a persisted fixed_plan
                # makes both the scan and the re-derivation unnecessary)
                probe, src = self._probe_blocks(src)
                ops = self._optimize_ops(ops, probe)
        plan = [op.name for op in ops]
        segments = plan_segments(ops)

        # segment-boundary checkpointing (only when forced via run_streaming
        # with a checkpoint_dir — run() routes checkpointed recipes here only
        # if the caller does so explicitly)
        op_cfgs = [op.config() for op in ops]
        sigs = recipe_prefix_sigs(op_cfgs)
        bounds: List[int] = []
        k = 0
        for seg in segments:
            k += len(seg.ops)
            bounds.append(k)
        ckpt = CheckpointManager(r.checkpoint_dir) if r.checkpoint_dir else None
        resumed_at, resumed_samples = 0, None
        if ckpt:
            resumed_at, resumed_samples = ckpt.resume_point(op_cfgs, allowed=set(bounds))

        if resumed_samples is not None:
            # original input size was persisted by the first (pre-crash) run;
            # fall back to the resumed-stage count if it predates that
            counter = {"n": ckpt.get_meta("n_in", len(resumed_samples))}
            if counted is not None:
                counted.close()  # release the probed file stream promptly
            src = iter(split_blocks(
                resumed_samples, n_workers=n_workers,
                total_hint_bytes=max(1, len(resumed_samples)) * 256))
        # sink first: a sink constructor failure must not strand a prefetch
        # thread that is already decoding blocks
        sink = BlockWriter(r.export_path) if r.export_path else None
        prefetcher: Optional[BlockPrefetcher] = None
        # prefetch only pays off over the lazy file-backed source — in-memory
        # blocks have no decode latency to overlap
        if prefetch and dataset is None and resumed_samples is None:
            src = prefetcher = BlockPrefetcher(src, depth=prefetch)
        # streaming insight: tap the source (the "load" snapshot) and every
        # segment's output stream — per-segment timeline, no barriers
        recorder = SegmentInsightRecorder() if r.insight else None
        if recorder is not None:
            src = recorder.tap("load", src)

        remaining = [(seg, end) for seg, end in zip(segments, bounds) if end > resumed_at]
        entries: List[dict] = []
        ok = False
        try:
            if ckpt and remaining:
                # checkpointing forces materialization at each segment
                # boundary (the stage must be persisted whole)
                blocks: List[SampleBlock] = []
                n_out = 0
                n_in_saved = resumed_samples is not None
                for seg, end in remaining:
                    is_last = end == bounds[-1]
                    blocks, ent, n_out = stream_segments(
                        src, [seg], engine, sink=sink if is_last else None,
                        collect=True, n_workers_hint=n_workers,
                        monitor=monitor, cancel=cancel, observer=recorder)
                    entries.extend(ent)
                    ckpt.save_stage(sigs[end - 1], end,
                                    [s for b in blocks for s in b.samples])
                    ckpt.gc()
                    if not n_in_saved:
                        # source fully drained by the first segment — persist
                        # the true input size for post-crash resumes
                        ckpt.set_meta("n_in", counter["n"])
                        n_in_saved = True
                    src = iter(blocks)
                if not materialize:
                    blocks = []
            else:
                blocks, entries, n_out = stream_segments(
                    src, [seg for seg, _ in remaining], engine, sink=sink,
                    collect=materialize, n_workers_hint=n_workers,
                    monitor=monitor, cancel=cancel, observer=recorder)
            ok = True
        finally:
            if sink is not None:
                sink.close(success=ok)  # failure keeps any previous export
            if prefetcher is not None:
                prefetcher.close()  # releases the fill thread on error paths

        errors = sum(len(op.errors) for op in ops)
        report = RunReport(
            recipe=r.name, n_in=counter["n"], n_out=n_out,
            seconds=clock.now() - t0, per_op=entries, plan=plan,
            resumed_at=resumed_at, errors=errors, streaming=True,
            insight=recorder.report() if recorder is not None else "",
            dispatch=list(getattr(engine, "dispatch_log", ())),
        )
        return DJDataset(blocks or [SampleBlock([])], engine), report

    # ------------------------------------------------------------------
    # barriered (per-op materializing) path
    # ------------------------------------------------------------------
    def run_barriered(self, dataset: Optional[DJDataset] = None,
                      monitor: Optional[List[dict]] = None,
                      cancel=None) -> tuple[DJDataset, RunReport]:
        sp = self._begin_run_span("barriered")
        try:
            ds, report = self._run_barriered_impl(dataset, monitor=monitor,
                                                  cancel=cancel)
        finally:
            self._pop_run_span(sp)
        self._finish_run_span(sp, report)
        return ds, report

    def _run_barriered_impl(self, dataset: Optional[DJDataset] = None,
                            monitor: Optional[List[dict]] = None,
                            cancel=None) -> tuple[DJDataset, RunReport]:
        r = self.recipe
        t0 = clock.now()
        engine = self._make_engine()
        if dataset is None:
            if not r.dataset_path:
                raise ValueError("recipe has no dataset_path and no dataset given")
            dataset = DJDataset.load(r.dataset_path, engine=engine,
                                     block_bytes=r.block_bytes)
        else:
            dataset = DJDataset(dataset.blocks, engine, dataset.lineage)
        n_in = len(dataset)

        ops, fixed = self._plan_ops()
        # probe + rule-based optimize (fusion & workload-aware reordering)
        if len(dataset) and not fixed:
            ops = self._optimize_ops(ops, dataset.samples())
        plan = [op.name for op in ops]

        # operator-level checkpoint resume
        resumed_at = 0
        ckpt = CheckpointManager(r.checkpoint_dir) if r.checkpoint_dir else None
        op_cfgs = [op.config() for op in ops]
        if ckpt:
            resumed_at, samples = ckpt.resume_point(op_cfgs)
            if samples is not None:
                dataset = DJDataset.from_samples(samples, engine)

        miner = InsightMiner() if r.insight else None
        if miner:
            miner.record("load", dataset.samples())

        monitor = monitor if monitor is not None else []
        # pre-seed one zero row per remaining op so async observers see the
        # full plan size (ops_total) up front, mirroring the streaming path
        rows = seed_op_entries(ops[resumed_at:])
        monitor.extend(rows)
        sigs = recipe_prefix_sigs(op_cfgs)
        errors = 0
        for i in range(resumed_at, len(ops)):
            if cancel is not None and cancel():
                raise ExecutionCancelled("barriered run cancelled")
            op = ops[i]
            step: List[dict] = []
            dataset = dataset.process(op, monitor=step)
            rows[i - resumed_at].update(step[0])
            errors += len(op.errors)
            if ckpt:
                ckpt.save_stage(sigs[i], i + 1, dataset.samples())
                ckpt.gc()
            if miner:
                miner.record(op.name, dataset.samples())

        if r.export_path:
            dataset.export(r.export_path)

        report = RunReport(
            recipe=r.name, n_in=n_in, n_out=len(dataset),
            seconds=clock.now() - t0, per_op=monitor, plan=plan,
            resumed_at=resumed_at,
            insight=miner.report() if miner else "", errors=errors,
            dispatch=list(getattr(engine, "dispatch_log", ())),
        )
        return dataset, report
