"""Operator taxonomy (paper §5.2, Appendix D.3).

Five atomic types — Formatter / Mapper / Filter / Deduplicator / Selector —
plus five compositional types — Grouper / Aggregator / FusedOP / ScriptOP /
HumanOP. A top-level abstract factory centralises parameter handling,
serialization, resource hints and the unified ``run()`` template method;
leaf OPs only implement their type's hook (``process_single``,
``compute_stats`` + ``keep``, ...), so each OP is self-contained and
individually testable.

Sample-level fault tolerance (paper §E.2): ``run()`` executes batches under
an exception manager; a failing batch is retried per-sample, and failing
samples are replaced by schema-compatible empty samples (dropped at the end
of the pipeline unless ``keep_failed``) while the error is recorded.
"""
from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from repro.core import clock
from repro.core import schema as S

Sample = Dict[str, Any]


@dataclasses.dataclass
class OpError:
    op: str
    index: int
    error: str


class Operator:
    """Abstract factory base for all OPs."""

    # resource hints used by the Adapter (paper §F.2)
    cpu_required: float = 1.0
    mem_required: int = 0  # bytes per worker
    gpu_mem_required: int = 0  # accelerator bytes per model instance (model OPs)
    uses_model: bool = False
    io_intensive: bool = False
    batched: bool = True
    default_batch_size: int = 1000

    # fusion metadata
    fusible: bool = False
    commutative: bool = True

    # columnar protocol: ops that opt in process whole ColumnBlocks without
    # the row-dict shim; ``pushdown_safe`` additionally marks ops cheap
    # enough (fully vectorized) to run driver-side at block decode
    # (fusion.plan_segments predicate pushdown)
    pushdown_safe: bool = False

    def __init__(self, **params):
        self.params = params
        # probed at runtime by the Adapter
        self.probed_speed: Optional[float] = None  # samples/sec
        self.errors: List[OpError] = []

    # ------------------------------------------------------------------
    @property
    def name(self) -> str:
        return getattr(self, "_name", type(self).__name__)

    def config(self) -> Dict[str, Any]:
        """Serialization: (name, params) round-trips through the registry."""
        return {"name": self.name, **self.params}

    def __repr__(self):
        return f"{type(self).__name__}({self.params})"

    # ------------------------------------------------------------------
    # per-type hooks
    # ------------------------------------------------------------------
    def process_batch(self, batch: List[Sample]) -> List[Sample]:
        raise NotImplementedError

    def setup(self) -> None:
        """Lazy init (model loading etc.) — called once before processing."""

    # ------------------------------------------------------------------
    # columnar protocol (struct-of-arrays blocks, repro.core.columnar)
    # ------------------------------------------------------------------
    def supports_columns(self) -> bool:
        """True when this op (as configured) can consume a ColumnBlock via
        :meth:`process_columns` with output equivalent to the row path.
        Engines run the longest columnar prefix of a chain before falling
        back to the row-dict shim; any exception inside the columnar path
        re-routes the block through the row path, so opting in never has to
        handle exotic data shapes — only raise."""
        return False

    def process_columns(self, block):
        """ColumnBlock -> ColumnBlock. Only called when
        :meth:`supports_columns` is True; must not mutate ``block``."""
        raise NotImplementedError(f"{self.name} has no columnar path")

    # ------------------------------------------------------------------
    # unified template method
    # ------------------------------------------------------------------
    def run(self, data, **kwargs):
        """Apply this OP to a DJDataset (or raw sample list)."""
        from repro.core.dataset import DJDataset

        if not isinstance(data, DJDataset):
            data = DJDataset.from_samples(list(data))
        return data.process(self, **kwargs)

    def run_batch_safe(self, batch: List[Sample], base_index: int = 0) -> List[Sample]:
        """Fault-tolerant batch execution (batch -> per-sample fallback)."""
        try:
            return self.process_batch(batch)
        except Exception:
            out: List[Sample] = []
            for j, s in enumerate(batch):
                try:
                    out.extend(self.process_batch([s]))
                except Exception as e:  # noqa: BLE001 — the exception manager
                    self.errors.append(
                        OpError(self.name, base_index + j, f"{type(e).__name__}: {e}")
                    )
                    out.append(S.empty_like(s))
            return out


class Formatter(Operator):
    """Loads / converts raw records into schema samples."""

    def format_single(self, record: Dict[str, Any]) -> Sample:
        raise NotImplementedError

    def process_batch(self, batch):
        return [self.format_single(r) for r in batch]


class Mapper(Operator):
    """Edits samples 1->1 (or 1->many when ``expands``)."""

    expands: bool = False

    def process_single(self, sample: Sample) -> Sample | List[Sample]:
        raise NotImplementedError

    def process_batch(self, batch):
        out: List[Sample] = []
        for s in batch:
            r = self.process_single(s)
            if self.expands and isinstance(r, list):
                out.extend(r)
            else:
                out.append(r)
        return out


CTX_KEY = "__ctx__"


def shared_words(sample: Sample) -> List[str]:
    """Per-sample shared context: tokenised words, computed ONCE per fused
    pass (the redundant work OP fusion eliminates — paper §F.1)."""
    ctx = sample.get(CTX_KEY)
    if ctx is None:
        ctx = {}
        sample[CTX_KEY] = ctx
    if "words" not in ctx:
        ctx["words"] = sample.get("text", "").split()
    return ctx["words"]


def clear_ctx(sample: Sample) -> Sample:
    sample.pop(CTX_KEY, None)
    return sample


class Filter(Operator):
    """compute_stats() fills sample['stats']; keep() decides retention."""

    fusible = True
    stats_keys: Sequence[str] = ()

    def compute_stats(self, sample: Sample) -> Sample:
        raise NotImplementedError

    def keep(self, sample: Sample) -> bool:
        raise NotImplementedError

    def process_batch(self, batch):
        out = []
        for s in batch:
            s = self.compute_stats(s)
            if self.keep(s):
                out.append(clear_ctx(s))
        return out

    def compute_stats_batch(self, batch: List[Sample]) -> List[Sample]:
        return [self.compute_stats(s) for s in batch]


class Deduplicator(Operator):
    """Dataset-level: computes hashes then drops duplicates (see dedup/).

    Streaming protocol: a Deduplicator that can run as an *incremental
    pipeline stage* (consuming and emitting blocks without a dataset-wide
    barrier) reports ``supports_streaming() -> True`` and provides a fresh
    per-run state object via ``streaming_state()`` (see
    ``repro.core.dedup.streaming``). ``fusion.plan_segments`` then plans it
    as a stateful stream segment instead of a barrier, and ``dedup()`` stays
    the barriered fallback.
    """

    dataset_level = True

    def dedup(self, samples: List[Sample]) -> List[Sample]:
        raise NotImplementedError

    def supports_streaming(self) -> bool:
        """True when this op (as configured) can run incrementally."""
        return False

    def streaming_state(self):
        """Fresh stateful stream-stage driver; consumed by ONE segment
        traversal (``state.stream_blocks(blocks, check_cancel)``)."""
        raise NotImplementedError(f"{self.name} has no streaming variant")

    def process_batch(self, batch):  # pragma: no cover — executed dataset-level
        return batch


class Selector(Operator):
    """Dataset-level rank/rule-based sampling."""

    dataset_level = True

    def select(self, samples: List[Sample]) -> List[Sample]:
        raise NotImplementedError

    def process_batch(self, batch):  # pragma: no cover
        return batch


class Grouper(Operator):
    """Dataset -> list of sample groups (feeds an Aggregator)."""

    dataset_level = True

    def group(self, samples: List[Sample]) -> List[List[Sample]]:
        raise NotImplementedError

    def process_batch(self, batch):  # pragma: no cover
        return batch


class Aggregator(Operator):
    """Combines a group of samples into one."""

    def aggregate(self, group: List[Sample]) -> Sample:
        raise NotImplementedError

    def process_batch(self, batch):
        # when run directly, treats the whole batch as one group
        return [self.aggregate(batch)]


# OPs that genuinely need the whole dataset before producing any output —
# pipeline barriers for the streaming executor (paper §E.3)
BARRIER_TYPES = (Deduplicator, Selector, Grouper, Aggregator)


class FusedOP(Operator):
    """Explicit batch-wise fusion of multiple OPs (paper Listing 4) plus the
    auto-fused Filter group produced by the optimizer (fusion.py)."""

    def __init__(self, ops: List[Operator], **params):
        super().__init__(**params)
        self.ops = ops
        self._name = "fused<" + ",".join(o.name for o in ops) + ">"

    def config(self):
        return {"name": "fused_op", "ops": [o.config() for o in self.ops], **self.params}

    def setup(self):
        for o in self.ops:
            o.setup()

    def supports_columns(self):
        return all(o.supports_columns() for o in self.ops)

    @property
    def pushdown_safe(self):  # type: ignore[override]
        return all(o.pushdown_safe for o in self.ops)

    @property
    def uses_model(self):  # type: ignore[override]
        return any(o.uses_model for o in self.ops)

    def process_columns(self, block):
        # cascaded columnar filtering: each op sees only the survivors of
        # the previous ones — the same work-saving shape as process_batch
        for op in self.ops:
            block = op.process_columns(block)
        return block

    def process_batch(self, batch):
        # one batch traversal with CASCADED filtering: the ops arrive in
        # probed-speed order (fusion.optimize), each filter's stats are
        # computed only on the survivors of the previous ones, and shared
        # context (e.g. tokenised words) is cached on the sample across the
        # fused group — both halves of the paper's fusion+reordering win.
        for op in self.ops:
            if isinstance(op, Filter) and type(op).process_batch is Filter.process_batch:
                batch = [s for s in (op.compute_stats(x) for x in batch) if op.keep(s)]
            else:  # custom batched filters (e.g. model-based) / mappers
                batch = op.process_batch(batch)
        return [clear_ctx(s) for s in batch]


class ScriptOP(Operator):
    """Wraps a user function / lambda / python file path."""

    def __init__(self, fn: Optional[Callable[[Sample], Sample]] = None,
                 script_path: Optional[str] = None, fn_name: str = "process", **params):
        super().__init__(**params)
        if fn is None and script_path:
            ns: Dict[str, Any] = {}
            with open(script_path) as f:
                exec(compile(f.read(), script_path, "exec"), ns)  # noqa: S102
            fn = ns[fn_name]
        if fn is None:
            raise ValueError("ScriptOP needs fn or script_path")
        self.fn = fn
        self._name = f"script<{getattr(fn, '__name__', 'lambda')}>"

    def process_batch(self, batch):
        return [self.fn(s) for s in batch]


class HumanOP(Operator):
    """Asynchronous human-in-the-loop annotation (paper: Label-Studio-backed).

    Offline reproduction: an annotation queue with a pluggable annotator
    callback (a human stand-in). ``submit`` is non-blocking; ``collect``
    integrates finished annotations back into samples, preserving the
    asynchronous control flow used for RLHF-style pipelines.
    """

    batched = False

    def __init__(self, annotator: Optional[Callable[[Sample], Dict[str, Any]]] = None,
                 annotation_key: str = "human", **params):
        super().__init__(**params)
        self.annotator = annotator or (lambda s: {"label": "ok"})
        self.annotation_key = annotation_key
        self.queue: List[Sample] = []
        self.done: List[Sample] = []

    def submit(self, samples: Iterable[Sample]) -> int:
        n = 0
        for s in samples:
            self.queue.append(s)
            n += 1
        return n

    def poll(self, max_items: Optional[int] = None) -> int:
        """Process pending annotations (simulates annotators finishing)."""
        n = 0
        while self.queue and (max_items is None or n < max_items):
            s = self.queue.pop(0)
            ann = self.annotator(s)
            s = dict(s)
            s.setdefault("meta", {})
            s["meta"] = dict(s["meta"], **{self.annotation_key: ann, "annotated_at": clock.now()})
            self.done.append(s)
            n += 1
        return n

    def collect(self) -> List[Sample]:
        out, self.done = self.done, []
        return out

    def process_batch(self, batch):
        self.submit(batch)
        self.poll()
        return self.collect()
