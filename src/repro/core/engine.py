"""Execution engines behind the DJDataset facade (paper §5.1, §E.1).

  * LocalEngine    — single-process (HF-Datasets-standalone analogue).
  * ParallelEngine — multi-worker host execution over pre-split blocks
    (Ray-mode analogue) with speculative re-dispatch of straggler blocks.
  * ShardedEngine  — vectorized OPs executed as jit'd SPMD programs over the
    jax device mesh (the TPU-native adaptation: per-sample numeric/stat OPs
    become data-parallel array programs; everything else falls back to the
    host path). Model-based OPs score batches through the model substrate.

Engines share one interface (``map_batches``), so OPs are engine-agnostic —
the Facade-pattern property the paper emphasises. All multi-worker dispatch
(ParallelEngine's batch and chain paths, LocalEngine's threaded chain
window) runs through the shared adaptive ``WindowedDispatcher``
(``repro.core.dispatch``): bounded adaptive in-flight window, speculative
straggler re-dispatch, failure retries, per-worker quarantine.
"""
from __future__ import annotations

import concurrent.futures as cf
import copy
import os
import threading
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from repro.core import clock, obs
from repro.core import schema as S
from repro.core.columnar import ColumnBlock
from repro.core.dispatch import (
    HealthRegistry, TaskPreempted, WindowedDispatcher, dispatch_policy,
)
from repro.core.ops_base import Operator, OpError
from repro.core.storage import SampleBlock, split_blocks

Sample = Dict[str, Any]


class EngineStats(dict):
    pass


class ChainOpFailure(Exception):
    """A hard failure (escaping the per-sample exception manager) while
    driving a block through op ``op_index`` of a chain. Picklable via the
    default (class, args) reduction, so worker processes can attribute the
    failing op instead of the consumer pinning errors to ``ops[0]``."""

    def __init__(self, op_index: int, op_name: str, message: str):
        super().__init__(op_index, op_name, message)
        self.op_index = op_index
        self.op_name = op_name
        self.message = message

    def __str__(self):
        return f"op[{self.op_index}] {self.op_name}: {self.message}"


def _iter_batches(samples: List[Sample], batch_size: int):
    for i in range(0, len(samples), batch_size):
        yield i, samples[i : i + batch_size]


def run_chain(
    ops: List[Operator], samples: List[Sample],
    batch_size: Optional[int] = None, drop_empty: bool = True,
    should_stop=None,
) -> Tuple[List[Sample], List[dict]]:
    """Drive one block's samples through a whole op chain in a single pass.

    This is the streaming executor's unit of work: one dispatch applies every
    op of a pipelineable segment to the block, instead of one dataset-wide
    barrier per op. Returns (out_samples, per-op stats) where each stats entry
    is {"op", "in", "out", "seconds", "errors"} for THIS block only — the
    caller aggregates across blocks so per-op lineage keeps working.

    ``should_stop`` is the dispatcher's preemption poll: checked between
    batches, a True result raises :class:`TaskPreempted` so a speculative
    loser frees its worker instead of draining the rest of the chain.
    """
    stats: List[dict] = []
    for k, op in enumerate(ops):
        t0 = time.perf_counter()
        n_in = len(samples)
        err0 = len(op.errors)
        try:
            bs = batch_size or op.default_batch_size
            out: List[Sample] = []
            with obs.annotate(f"op:{op.name}"):
                for i in range(0, len(samples), bs):
                    if should_stop is not None and should_stop():
                        raise TaskPreempted(f"chain preempted at op[{k}] {op.name}")
                    out.extend(op.run_batch_safe(samples[i : i + bs], i))
                if drop_empty:
                    out = [s for s in out if not S.is_empty(s)]
        except (ChainOpFailure, TaskPreempted):
            raise
        except Exception as e:  # escaped the per-sample exception manager
            raise ChainOpFailure(k, op.name, f"{type(e).__name__}: {e}") from e
        samples = out
        stats.append({
            "op": op.name, "in": n_in, "out": len(samples),
            "seconds": time.perf_counter() - t0,
            "errors": len(op.errors) - err0,
        })
    return samples, stats


def _columnar_ok(block) -> bool:
    """A block is eligible for the columnar fast path only while nobody has
    materialized its row dicts (after that the dicts are authoritative) and
    it carries no empty samples (columnar filters would keep rows that
    ``run_chain``'s drop_empty discards)."""
    return (isinstance(block, ColumnBlock) and not block.materialized
            and not block.may_have_empty)


def _columnar_prefix(
    ops: List[Operator], block, should_stop=None,
) -> Tuple[Any, List[dict], int]:
    """Run the longest columnar prefix of ``ops`` directly on the
    ColumnBlock — no row dicts. Returns (block, stats, k) where ``k`` is the
    number of ops consumed; the caller runs ``ops[k:]`` through the row-dict
    shim. Any exception inside an op's columnar path (exotic data shape,
    wrong column kind) just ends the prefix — the op reruns on rows, so
    opting in is always safe."""
    stats: List[dict] = []
    k = 0
    while k < len(ops) and _columnar_ok(block):
        op = ops[k]
        try:
            if not op.supports_columns():
                break
        except Exception:  # noqa: BLE001 — opt-in probe must never fail the chain
            break
        if should_stop is not None and should_stop():
            raise TaskPreempted(f"chain preempted at op[{k}] {op.name}")
        t0 = time.perf_counter()
        n_in = len(block)
        try:
            with obs.annotate(f"op:{op.name}"):
                op.setup()
                nxt = op.process_columns(block)
        except TaskPreempted:
            raise
        except Exception:  # noqa: BLE001 — fall back to the row path from op k
            break
        stats.append({"op": op.name, "in": n_in, "out": len(nxt),
                      "seconds": time.perf_counter() - t0, "errors": 0})
        block = nxt
        k += 1
    return block, stats, k


def _chain_failure(ops: List[Operator], blk: SampleBlock, err: dict):
    """Pass-through outcome for a chain block whose every dispatch failed:
    synthesized per-op stats plus an OpError pinned to the op that actually
    failed (``err["op_index"]`` from ChainOpFailure, 0 when unattributable),
    so per-op lineage still accounts for the block's samples."""
    k = err.get("op_index", -1)
    k = k if 0 <= k < len(ops) else 0
    stats = [{"op": o.name, "in": len(blk.samples), "out": len(blk.samples),
              "seconds": 0.0, "errors": 1 if j == k else 0}
             for j, o in enumerate(ops)]
    ops[k].errors.append(OpError(
        ops[k].name, -1,
        f"worker failed on chain block ({err.get('attempts', 1)} attempts): "
        f"{err.get('error')}"))
    return list(blk.samples), stats


class LocalEngine:
    name = "local"

    def __init__(self, n_threads: int = 1, straggler_factor: float = 3.0,
                 speculate: bool = True, health_path: Optional[str] = None,
                 mem_budget: Optional[int] = None):
        self.n_threads = n_threads
        self.straggler_factor = straggler_factor
        self.speculate = speculate
        self.mem_budget = mem_budget  # resident in-flight block bytes cap
        self.redispatches = 0  # cumulative; per-call counts live in dispatch_log
        self.dispatch_log: List[dict] = []
        # cross-run worker-slot health (docs/runtime.md): quarantines persist
        # to health_path; previously-quarantined slots start on probation
        self.health = HealthRegistry(health_path) if health_path else None

    def dispatch_policy(self) -> dict:
        return {"engine": self.name,
                **dispatch_policy(self.n_threads, self.straggler_factor,
                                  self.speculate and self.n_threads > 1, 3)}

    def map_batches(
        self, op: Operator, blocks: List[SampleBlock], batch_size: int
    ) -> Tuple[List[SampleBlock], EngineStats]:
        op.setup()
        t0 = clock.now()
        out_blocks: List[SampleBlock] = []
        n_in = 0
        threads = self.n_threads if op.io_intensive else 1
        # hierarchical parallelism: multithreading for I/O-bound OPs overlaps
        # I/O latency with compute (paper §F.2, Fig. 10b); one pool serves
        # every block of the call
        pool = cf.ThreadPoolExecutor(threads) if threads > 1 else None
        try:
            for blk in blocks:
                results: List[List[Sample]] = []
                if pool is not None:
                    futs = [
                        pool.submit(op.run_batch_safe, b, i)
                        for i, b in _iter_batches(blk.samples, batch_size)
                    ]
                    results = [f.result() for f in futs]
                else:
                    for i, b in _iter_batches(blk.samples, batch_size):
                        results.append(op.run_batch_safe(b, i))
                merged: List[Sample] = [s for r in results for s in r]
                n_in += len(blk)
                out_blocks.append(SampleBlock(merged))
        finally:
            if pool is not None:
                pool.shutdown()
        dt = clock.now() - t0
        return out_blocks, EngineStats(seconds=dt, samples=n_in, engine=self.name)

    def map_block_chain(
        self, ops: List[Operator], blocks: Iterable[SampleBlock],
        batch_size: Optional[int] = None,
    ) -> Iterator[Tuple[SampleBlock, List[dict]]]:
        """Streaming: drive each block through the whole op chain, yielding
        (out_block, per-op block stats) as soon as the block completes.

        With ``n_threads > 1`` and an I/O-intensive op in the chain, blocks
        run through the chain concurrently in a bounded thread window
        (hierarchical parallelism, paper §F.2) — results stay in input order.
        Each thread gets its own op clones so error bookkeeping stays
        race-free; non-reconstructible ops fall back to the sequential path.
        """
        for op in ops:
            op.setup()
        threads = self.n_threads if any(op.io_intensive for op in ops) else 1
        cfgs = None
        if threads > 1:
            try:
                cfgs = [op.config() for op in ops]
                from repro.core.registry import create_op

                for c in cfgs:
                    create_op(c)  # reconstructibility probe
            except Exception:
                cfgs = None
        if threads <= 1 or cfgs is None:
            for blk in blocks:
                cur, cstats, k = _columnar_prefix(ops, blk)
                if k == len(ops):
                    # whole chain ran on columns: zero row dicts built
                    yield cur, cstats
                    continue
                out, stats = run_chain(ops[k:], cur.samples, batch_size)
                # nbytes left lazy (0): output blocks are consumed immediately
                # by the next segment or sink, never re-split by size
                yield SampleBlock(out, nbytes=0), cstats + stats
            return

        from repro.core.registry import create_op

        tls = threading.local()  # one clone chain per worker thread, not per block

        def work(blk, should_stop=None):
            local_ops = getattr(tls, "ops", None)
            if local_ops is None:
                local_ops = [create_op(c) for c in cfgs]
                for o in local_ops:
                    o.setup()
                tls.ops = local_ops
            for o in local_ops:
                # reused clones must not re-report past blocks; cleared on
                # entry (not after run_chain) so a hard chain failure can't
                # leak this block's errors into the thread's next block
                o.errors = []
            # thread pools share objects (the process pool's pickling copies
            # per dispatch): columnar transforms never mutate their input, so
            # the prefix can run on the SHARED block even under speculation;
            # the row remainder gets a private decode (or deep copy) so a
            # backup attempt never mutates dicts the original still writes.
            cur, cstats, k = _columnar_prefix(local_ops, blk, should_stop)
            if k == len(local_ops):
                return cur, cstats, []
            if isinstance(cur, ColumnBlock):
                samples = cur.decode_rows()  # private, uncached
            else:
                samples = copy.deepcopy(cur.samples)
            out, stats = run_chain(local_ops[k:], samples, batch_size,
                                   should_stop=should_stop)
            errs = [(j, e) for j, o in enumerate(local_ops) for e in o.errors]
            return out, cstats + stats, errs

        with cf.ThreadPoolExecutor(threads) as pool:
            disp = WindowedDispatcher(
                pool, threads, straggler_factor=self.straggler_factor,
                speculate=self.speculate,
                label="+".join(op.name for op in ops),
                log=self.dispatch_log, meta={"engine": self.name},
                # plain dict: thread-pool workers share the driver's heap
                preempt_board={}, health=self.health,
                mem_budget=self.mem_budget)
            gen = disp.run(blocks, work, lambda blk: (blk,))
            try:
                for blk, payload, err in gen:
                    if err is None:
                        out, stats, errs = payload
                        for k, e in errs:  # merged on the main thread — no races
                            ops[k].errors.append(e)
                    else:
                        out, stats = _chain_failure(ops, blk, err)
                    if isinstance(out, ColumnBlock):
                        yield out, stats
                    else:
                        yield SampleBlock(out, nbytes=0), stats
            finally:
                gen.close()
                if disp.summary is not None:
                    self.redispatches += disp.summary["redispatches"]


def _worker_apply(op_config: Dict[str, Any], samples: List[Sample], batch_size: int):
    """Runs in a worker process: rebuild the OP from config, apply safely."""
    from repro.core.registry import create_op

    op = create_op(op_config)
    op.setup()
    out: List[Sample] = []
    for i in range(0, len(samples), batch_size):
        out.extend(op.run_batch_safe(samples[i : i + batch_size], i))
    return out, [e.__dict__ for e in op.errors]


def _worker_apply_chain(
    op_configs: List[Dict[str, Any]], payload,
    batch_size: Optional[int] = None, should_stop=None,
):
    """Runs in a worker process: rebuild the whole segment chain from configs
    and drive the block through it in one dispatch. ``payload`` is either a
    raw sample list or a ColumnBlock (the parallel engine ships columns —
    one pickled buffer per column instead of N row dicts); the row-dict shim
    appears only past the chain's columnar prefix, and the output is
    re-encoded to columns so the return trip ships buffers too.
    ``should_stop`` is the dispatcher's preemption poll (a Manager-proxy
    read), threaded into ``run_chain`` so a losing speculative submission
    exits at the next batch boundary instead of draining."""
    from repro.core.registry import create_op

    ops = []
    for k, c in enumerate(op_configs):
        try:
            op = create_op(c)
            op.setup()
        except Exception as e:  # attribute rebuild/setup failures to op k too
            raise ChainOpFailure(k, str(c.get("name", "?")),
                                 f"{type(e).__name__}: {e}") from e
        ops.append(op)
    cstats: List[dict] = []
    columnar_in = isinstance(payload, ColumnBlock)
    if columnar_in:
        payload, cstats, kp = _columnar_prefix(ops, payload, should_stop)
        if kp == len(ops):
            return payload, cstats, []
        ops = ops[kp:]
        if isinstance(payload, ColumnBlock):
            payload = payload.samples
    out, stats = run_chain(ops, payload, batch_size, should_stop=should_stop)
    stats = cstats + stats
    # errors carry the op's index in the FULL chain (prefix ops report none)
    # — attribution by name would merge two instances of the same OP class
    off = len(cstats)
    errors = [(off + k, e.__dict__) for k, op in enumerate(ops) for e in op.errors]
    if columnar_in and cstats:
        # return trip ships column buffers too — but only when the columnar
        # prefix actually ran: a chain that fell straight to rows gains
        # nothing from re-encoding, it would just pay encode+decode
        try:
            out = ColumnBlock.from_samples(out)
        except Exception:  # noqa: BLE001 — exotic rows ship as row dicts
            pass
    return out, stats, errors


class ParallelEngine:
    """Multi-process engine; all dispatch runs through the shared
    :class:`~repro.core.dispatch.WindowedDispatcher`.

    Speculative execution: once ``min_completions`` blocks finish, any block
    running longer than ``straggler_factor`` x the median completion time
    gets a backup submission; first finisher wins, the loser is cancelled.
    A worker that fails ``worker_failure_limit`` tasks is quarantined (its
    blocks re-dispatch to healthy workers instead of passing through).
    """

    name = "parallel"

    def __init__(self, n_workers: Optional[int] = None, straggler_factor: float = 3.0,
                 speculate: bool = True, min_completions: Optional[int] = None,
                 worker_failure_limit: int = 3, health_path: Optional[str] = None,
                 mem_budget: Optional[int] = None):
        self.n_workers = n_workers or max(1, (os.cpu_count() or 2) - 1)
        self.straggler_factor = straggler_factor
        self.speculate = speculate
        self.mem_budget = mem_budget  # resident in-flight block bytes cap
        self.min_completions = min_completions
        self.worker_failure_limit = worker_failure_limit
        self.redispatches = 0  # cumulative; per-call counts in EngineStats/dispatch_log
        self.dispatch_log: List[dict] = []
        self.health = HealthRegistry(health_path) if health_path else None
        self._preempt_mgr: Any = None  # lazy Manager; False = unavailable
        self._preempt_dict: Any = None

    def _dispatcher(self, pool, label: str, preempt_board=None) -> WindowedDispatcher:
        return WindowedDispatcher(
            pool, self.n_workers, straggler_factor=self.straggler_factor,
            speculate=self.speculate, min_completions=self.min_completions,
            worker_failure_limit=self.worker_failure_limit,
            label=label, log=self.dispatch_log, meta={"engine": self.name},
            preempt_board=preempt_board, health=self.health,
            mem_budget=self.mem_budget)

    def _preempt_board(self):
        """Manager-backed shared dict readable from worker processes: the
        preemption channel for the chain path. ONE Manager per engine (its
        server process costs ~100ms to start — per-segment churn would pay
        that on every chain call), shared across dispatch calls; dispatcher
        key namespacing keeps sequential runs from colliding. Returns None
        when the Manager can't start (preemption then degrades to the old
        cancel-only behavior rather than failing the run); the Manager dies
        with the engine (its finalizer runs on GC / interpreter exit)."""
        if self._preempt_mgr is False:
            return None
        if self._preempt_mgr is None:
            try:
                import multiprocessing

                self._preempt_mgr = multiprocessing.Manager()
                self._preempt_dict = self._preempt_mgr.dict()
            except Exception:  # noqa: BLE001 — sandboxed envs without semaphores
                self._preempt_mgr = False
                return None
        return self._preempt_dict

    def dispatch_policy(self) -> dict:
        return {"engine": self.name,
                **dispatch_policy(self.n_workers, self.straggler_factor,
                                  self.speculate, self.worker_failure_limit)}

    def _fallback(self) -> "LocalEngine":
        # non-reconstructible op or a model op: host path, but any dispatch
        # summaries it logs still land in THIS engine's report
        fb = LocalEngine()
        fb.dispatch_log = self.dispatch_log
        return fb

    def map_batches(self, op, blocks, batch_size):
        if op.uses_model:  # the device belongs to this process, not to workers
            return self._fallback().map_batches(op, blocks, batch_size)
        try:
            cfg = op.config()
            from repro.core.registry import create_op
            create_op(cfg)  # picklability / reconstructibility probe
        except Exception:
            return self._fallback().map_batches(op, blocks, batch_size)

        t0 = clock.now()
        out_blocks: List[SampleBlock] = []
        with cf.ProcessPoolExecutor(self.n_workers) as pool:
            disp = self._dispatcher(pool, label=op.name)
            for idx, (blk, payload, err) in enumerate(disp.run(
                    blocks, _worker_apply,
                    lambda b: (cfg, b.samples, batch_size))):
                if err is None:
                    out, errs = payload
                    for e in errs:
                        op.errors.append(OpError(**e))
                    out_blocks.append(SampleBlock(out))
                else:
                    # every submission for this block failed: pass the input
                    # through so the run completes, but surface the failure —
                    # a silent pass-through resurrects rows a Filter should
                    # have dropped
                    out_blocks.append(SampleBlock(list(blk.samples)))
                    op.errors.append(OpError(
                        op.name, idx,
                        f"worker failed on block {idx} "
                        f"({err['attempts']} attempts): {err['error']}"))
        summary = disp.summary or {}
        self.redispatches += summary.get("redispatches", 0)
        return out_blocks, EngineStats(
            seconds=clock.now() - t0,
            samples=sum(len(b) for b in blocks),
            engine=self.name,
            # per-call delta (the cumulative count previously reported here
            # inflated later runs' stats)
            redispatches=summary.get("redispatches", 0),
            quarantined=len(summary.get("quarantined", ())),
        )

    def map_block_chain(
        self, ops: List[Operator], blocks: Iterable[SampleBlock],
        batch_size: Optional[int] = None,
    ) -> Iterator[Tuple[SampleBlock, List[dict]]]:
        """Streaming: one worker dispatch drives a block through the whole
        segment chain via the shared WindowedDispatcher — bounded adaptive
        in-flight window, speculative straggler re-dispatch, worker
        quarantine. Results are yielded in input order so outputs stay
        deterministic (a speculative backup computes the identical block).
        A chain holding a model op runs in this process: the device belongs
        to one process, and forked workers would each open it."""
        if any(op.uses_model for op in ops):
            yield from self._fallback().map_block_chain(ops, blocks, batch_size)
            return
        try:
            cfgs = [op.config() for op in ops]
            from repro.core.registry import create_op

            for c in cfgs:
                create_op(c)  # picklability / reconstructibility probe
        except Exception:
            yield from self._fallback().map_block_chain(ops, blocks, batch_size)
            return

        board = self._preempt_board() if self.speculate else None
        with cf.ProcessPoolExecutor(self.n_workers) as pool:
            disp = self._dispatcher(pool, label="+".join(op.name for op in ops),
                                    preempt_board=board)
            # columnar blocks ship whole: one pickled buffer per column, not
            # N row dicts (materialized blocks fall back to their row lists)
            gen = disp.run(
                blocks, _worker_apply_chain,
                lambda b: (cfgs,
                           b if _columnar_ok(b) else b.samples,
                           batch_size))
            try:
                for blk, payload, err in gen:
                    if err is None:
                        out, stats, errs = payload
                        for k, e in errs:
                            ops[k].errors.append(OpError(**e))
                    else:
                        out, stats = _chain_failure(ops, blk, err)
                    if isinstance(out, ColumnBlock):
                        yield out, stats
                    else:
                        yield SampleBlock(out, nbytes=0), stats
            finally:
                gen.close()
                if disp.summary is not None:
                    self.redispatches += disp.summary["redispatches"]


class ShardedEngine:
    """SPMD engine: vectorized OPs run as jit'd array programs on the mesh.

    An OP opts in by implementing
    ``compute_stats_arrays(cols) -> (stat_name, np.ndarray)`` — the engine
    builds padded device arrays sharded over ``data`` and executes the OP's
    jitted kernel; non-vectorized OPs fall back to the host path.
    """

    name = "sharded"

    # device-sized super-batch: consecutive blocks are merged until this many
    # rows are pending before a vectorized chain dispatch, so jit'd array
    # programs see a few large arrays instead of many block-sized ones
    SUPER_BATCH_ROWS = 4096

    def __init__(self, mesh=None, fallback: Optional[LocalEngine] = None,
                 super_batch_rows: Optional[int] = None):
        self.mesh = mesh
        self.fallback = fallback or LocalEngine()
        self.super_batch_rows = max(1, super_batch_rows or self.SUPER_BATCH_ROWS)

    @property
    def dispatch_log(self) -> List[dict]:
        return self.fallback.dispatch_log  # host-path dispatches land here

    def dispatch_policy(self) -> dict:
        # vectorized chains run in-process (no dispatch window); the host
        # fallback path inherits the fallback engine's adaptive policy
        return {"engine": self.name, "vectorized": "in-process",
                "fallback": self.fallback.dispatch_policy()}

    def map_batches(self, op, blocks, batch_size):
        fn = getattr(op, "compute_stats_arrays", None)
        if fn is None or not hasattr(op, "keep"):
            return self.fallback.map_batches(op, blocks, batch_size)
        op.setup()
        t0 = clock.now()
        out_blocks = []
        n = 0
        for blk in blocks:
            stat_name, values = fn(blk.samples)  # vectorized (numpy/jax)
            kept = []
            for s, v in zip(blk.samples, np.asarray(values)):
                s.setdefault("stats", {})[stat_name] = float(v)
                if op.keep(s):
                    kept.append(s)
            out_blocks.append(SampleBlock(kept))
            n += len(blk)
        return out_blocks, EngineStats(seconds=clock.now() - t0, samples=n, engine=self.name)

    def _chain_samples(
        self, ops: List[Operator], samples: List[Sample],
        batch_size: Optional[int],
    ) -> Tuple[List[Sample], List[dict]]:
        """Drive one batch of samples through the chain: vectorized OPs run
        as array programs, the rest fall back to the host chain."""
        stats: List[dict] = []
        for op in ops:
            fn = getattr(op, "compute_stats_arrays", None)
            if fn is not None and hasattr(op, "keep") and samples:
                t0 = time.perf_counter()
                n_in = len(samples)
                stat_name, values = fn(samples)
                kept = []
                for s, v in zip(samples, np.asarray(values)):
                    s.setdefault("stats", {})[stat_name] = float(v)
                    if op.keep(s):
                        kept.append(s)
                samples = kept
                stats.append({
                    "op": op.name, "in": n_in, "out": len(samples),
                    "seconds": time.perf_counter() - t0, "errors": 0,
                })
            else:
                samples, sub = run_chain([op], samples, batch_size)
                stats.extend(sub)
        return samples, stats

    def _full_columnar(self, ops: List[Operator], blk
                       ) -> Optional[Tuple[Any, List[dict]]]:
        """Zero-copy hand-off (ROADMAP carry-over): a ColumnBlock whose
        ENTIRE chain takes the columnar path skips the row-shim decode —
        columns flow straight through ``process_columns``, never touching
        the super-batch row buffer. All-or-nothing: if any op bails
        mid-prefix, return None and rerun the whole chain on rows (columnar
        transforms never mutate their input, so the rerun is safe) — a
        partial prefix must NOT feed survivors into ``pending``, where the
        remaining ops would be applied a second time."""
        if not _columnar_ok(blk):
            return None
        try:
            if not all(op.supports_columns() for op in ops):
                return None
        except Exception:  # noqa: BLE001 — opt-in probe must never fail the chain
            return None
        cur, cstats, k = _columnar_prefix(ops, blk)
        if k == len(ops):
            return cur, cstats
        return None

    def map_block_chain(
        self, ops: List[Operator], blocks: Iterable[SampleBlock],
        batch_size: Optional[int] = None,
    ) -> Iterator[Tuple[SampleBlock, List[dict]]]:
        """Streaming with super-batching (ROADMAP item): when the chain has a
        vectorized OP, consecutive blocks are accumulated into device-sized
        super-batches (``super_batch_rows``) before dispatch, so the jit'd
        array program runs over one large sharded array instead of once per
        host-sized block — fewer dispatches, full mesh occupancy. Chains with
        no vectorized OP keep per-block latency. ColumnBlocks whose whole
        chain is columnar bypass both paths zero-copy (``_full_columnar``);
        any pending super-batch flushes first so row order is preserved."""
        for op in ops:
            op.setup()
        vectorized = any(
            getattr(op, "compute_stats_arrays", None) is not None
            and hasattr(op, "keep") for op in ops)
        if not vectorized:
            for blk in blocks:
                res = self._full_columnar(ops, blk)
                if res is not None:
                    yield res
                    continue
                samples, stats = self._chain_samples(ops, blk.samples, batch_size)
                yield SampleBlock(samples, nbytes=0), stats
            return

        pending: List[Sample] = []
        for blk in blocks:
            res = self._full_columnar(ops, blk)
            if res is not None:
                if pending:  # flush BEFORE the direct yield: keep row order
                    samples, stats = self._chain_samples(ops, pending, batch_size)
                    pending = []
                    yield SampleBlock(samples, nbytes=0), stats
                yield res
                continue
            pending.extend(blk.samples)
            if len(pending) >= self.super_batch_rows:
                samples, stats = self._chain_samples(ops, pending, batch_size)
                pending = []
                yield SampleBlock(samples, nbytes=0), stats
        if pending:
            samples, stats = self._chain_samples(ops, pending, batch_size)
            yield SampleBlock(samples, nbytes=0), stats


def make_engine(kind: str = "local", **kw):
    return {"local": LocalEngine, "parallel": ParallelEngine, "sharded": ShardedEngine}[kind](**kw)
