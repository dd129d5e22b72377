"""Unified observability subsystem: structured trace spans + bounded
metrics registry (ISSUE 8, ROADMAP item 3 on-ramp).

**Spans.** A :class:`Span` is one timed unit of work — job, shard task,
run, segment, dispatch window, block, kernel batch — carrying a
``trace_id`` shared by every span of one logical job and a ``parent_id``
linking it into a tree. Ids are minted at `Executor.run` / cluster
``submit`` and *propagated*, not re-minted, across every boundary the
runtime crosses: worker IPC (the dispatcher ships a trace context into
``_guarded`` and the block span travels back in the result tuple),
cluster lease execution (the recipe carries ``trace``), and ``~s/~r/~fin``
shard tasks (the shard spec inherits the parent trace). A sharded job
killed mid-dedup and failed over therefore still yields ONE merged trace:
spans are deduped by ``span_id`` at merge time, and spans from the killed
attempt that never flushed are simply absent — no orphans, because every
emitted span's parent chain roots at the job span written by the accepted
``complete()``.

**Per-process spill.** Each process appends finished spans to
``<obs_dir>/spans-<pid>-<uniq>.jsonl`` (O_APPEND, line-atomic on local
and NFS-style shared filesystems — same trick as the cluster event log).
``merge_trace(obs_dir, trace_id)`` reads every spill, filters, dedupes
and sorts — that is the driver-side merge.

**Metrics.** :class:`MetricsRegistry` holds bounded counters / gauges /
fixed-bucket histograms (queue-wait, block compute, redispatches,
resident bytes). ``snapshot()`` is JSON-safe; ``merge()`` folds
per-process snapshots into cluster totals for ``GET /metrics``.

**Timed scopes.** Work repeated per block (shingling, a signature
dispatch, an export write) goes through :class:`timed`: it adds its
seconds and counts to counters and buffers no span, so a long job never
fills ``MAX_SPANS``.

**The profiler's clock.** While a JAX profiler session records in this
process, every :func:`span`, :class:`timed` scope and :func:`annotate`
also opens a ``jax.profiler.TraceAnnotation`` of the same name, so the
device trace shows what the host was doing in each idle gap. obs never
imports JAX: a process that has not imported it runs no profiler.

Tracing defaults ON (cheap: in-memory append per span, one counter
update per timed scope) but is fully disabled with ``DJ_OBS=0`` or
:func:`disable`.

All timestamps come from :mod:`repro.core.clock` so tests can inject a
fake clock and span merging stays deterministic.
"""
from __future__ import annotations

import contextlib
import os
import sys
import threading
import uuid
from typing import Any, Dict, Iterable, List, Optional

from repro.core import clock
from repro.core.storage import json_dumps, json_loads

MAX_SPANS = 4096        # per-process in-memory bound; overflow -> dropped count
MAX_METRICS = 512       # distinct metric names per registry

# fixed histogram buckets (seconds) — chosen to straddle queue-waits of
# microseconds through multi-minute stragglers; fixed so per-process
# snapshots merge by simple elementwise addition
SECONDS_BUCKETS = (0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0, 60.0, float("inf"))


def new_id() -> str:
    return uuid.uuid4().hex[:16]


def enabled() -> bool:
    return _state.enabled


def disable() -> None:
    _state.enabled = False


def enable() -> None:
    _state.enabled = True


# ----------------------------------------------------------------------
# Spans
# ----------------------------------------------------------------------
class Span:
    """One timed unit of work. ``end()`` stamps the duration and hands the
    span to the tracer buffer; ``to_dict()`` is the persisted schema."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "kind",
                 "t0", "dur", "attrs", "pid", "tid", "_done")

    def __init__(self, trace_id: str, name: str, kind: str = "span",
                 parent_id: Optional[str] = None,
                 span_id: Optional[str] = None,
                 t0: Optional[float] = None):
        self.trace_id = trace_id
        self.span_id = span_id or new_id()
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.t0 = clock.now() if t0 is None else t0
        self.dur = 0.0
        self.attrs: Dict[str, Any] = {}
        self.pid = os.getpid()
        self.tid = threading.get_ident() % 100000
        self._done = False

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self, t1: Optional[float] = None) -> "Span":
        if not self._done:
            self._done = True
            self.dur = max(0.0, (clock.now() if t1 is None else t1) - self.t0)
            _state.record(self)
        return self

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "kind": self.kind, "t0": self.t0, "dur": self.dur,
            "pid": self.pid, "tid": self.tid, "attrs": self.attrs,
        }


class _TracerState:
    """Process-global tracer: bounded span buffer + ambient parent stack
    (thread-local) + optional spill directory."""

    def __init__(self):
        self.enabled = os.environ.get("DJ_OBS", "1") not in ("0", "false", "")
        self.lock = threading.Lock()
        self.spans: List[Dict[str, Any]] = []
        self.dropped = 0
        self.spill_dir: Optional[str] = None
        self._spill_path: Optional[str] = None
        self._local = threading.local()

    # -- ambient context ------------------------------------------------
    def stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current(self) -> Optional[Span]:
        st = self.stack()
        return st[-1] if st else None

    def timed_stack(self) -> list:
        st = getattr(self._local, "timed", None)
        if st is None:
            st = self._local.timed = []
        return st

    # -- recording ------------------------------------------------------
    def record(self, span: Span) -> None:
        if not self.enabled:
            return
        self.record_dict(span.to_dict())

    def record_dict(self, d: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        with self.lock:
            if len(self.spans) >= MAX_SPANS:
                self.dropped += 1
                return
            self.spans.append(d)

    def configure(self, spill_dir: Optional[str]) -> None:
        with self.lock:
            self.spill_dir = spill_dir
            self._spill_path = None
            if spill_dir:
                os.makedirs(spill_dir, exist_ok=True)

    def flush(self) -> Optional[str]:
        """Append buffered spans to the per-process spill file and clear
        the buffer. No-op without a spill dir (in-process runs keep spans
        in memory for RunReport.trace)."""
        with self.lock:
            if not self.spill_dir or not self.spans:
                return self._spill_path
            if self._spill_path is None:
                self._spill_path = os.path.join(
                    self.spill_dir,
                    f"spans-{os.getpid()}-{uuid.uuid4().hex[:6]}.jsonl")
            batch, self.spans = self.spans, []
        buf = b"".join(json_dumps(d) + b"\n" for d in batch)
        fd = os.open(self._spill_path,
                     os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, buf)
            os.fsync(fd)
        finally:
            os.close(fd)
        return self._spill_path

    def drain(self, trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
        """Pop buffered spans (optionally one trace's) out of memory."""
        with self.lock:
            if trace_id is None:
                out, self.spans = self.spans, []
            else:
                out = [s for s in self.spans if s["trace_id"] == trace_id]
                self.spans = [s for s in self.spans
                              if s["trace_id"] != trace_id]
        return out

    def reset(self) -> None:
        with self.lock:
            self.spans = []
            self.dropped = 0
            self.spill_dir = None
            self._spill_path = None
        self.enabled = os.environ.get("DJ_OBS", "1") not in ("0", "false", "")


_state = _TracerState()


def tracer() -> _TracerState:
    return _state


def configure(spill_dir: Optional[str]) -> None:
    _state.configure(spill_dir)


def flush() -> Optional[str]:
    return _state.flush()


def drain(trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    return _state.drain(trace_id)


def reset() -> None:
    _state.reset()
    _metrics.reset()


def current_span() -> Optional[Span]:
    return _state.current()


def start_span(trace_id: Optional[str], name: str, kind: str = "span",
               parent_id: Optional[str] = None, **kw) -> Optional[Span]:
    """Create a span (NOT pushed on the ambient stack). Returns None when
    tracing is disabled or there is no trace context — callers guard with
    ``if span: span.end()`` and pay ~nothing on the disabled path."""
    if not _state.enabled or not trace_id:
        return None
    return Span(trace_id, name, kind=kind, parent_id=parent_id, **kw)


def _annotation(name: str):
    """An open ``jax.profiler.TraceAnnotation`` named ``name`` while a
    profiler session records in this process, else None. Close it with
    ``__exit__``."""
    jax = sys.modules.get("jax")
    prof = getattr(jax, "profiler", None)
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return None
    ann = prof.TraceAnnotation(name)
    ann.__enter__()
    return ann


@contextlib.contextmanager
def span(trace_id: Optional[str], name: str, kind: str = "span",
         parent_id: Optional[str] = None, **kw):
    """Context manager: opens a span parented to the ambient span (unless
    ``parent_id`` given), pushes it as the ambient parent, ends on exit.
    Yields None when disabled."""
    if not _state.enabled or not trace_id:
        yield None
        return
    if parent_id is None:
        cur = _state.current()
        parent_id = cur.span_id if cur is not None else None
    sp = Span(trace_id, name, kind=kind, parent_id=parent_id, **kw)
    _state.stack().append(sp)
    ann = _annotation(name)
    try:
        yield sp
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        st = _state.stack()
        if st and st[-1] is sp:
            st.pop()
        sp.end()


@contextlib.contextmanager
def annotate(name: str):
    """A profiler annotation alone (no span, no counter): names a stretch
    of host work on the device trace's clock while a profiler records."""
    ann = _annotation(name) if _state.enabled else None
    try:
        yield
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)


class timed:
    """Timed scope for per-block work: on exit adds its seconds to the
    counter ``<name>_seconds`` and ``counts`` (plus whatever :meth:`add`
    gave it) to their counters; records no span. Scopes nest per thread,
    and a scope's seconds leave out those of the scopes nested in it, so
    the ``_seconds`` counters of one thread never count a stretch twice.
    While a profiler records, the scope is also an annotation ``name``
    whose metadata carries the counts. Does nothing when obs is disabled.
    """

    __slots__ = ("name", "counts", "nested", "_t0", "_ann")

    def __init__(self, name: str, counts: Optional[Dict[str, float]] = None):
        self.name = name
        self.counts = {k: float(v) for k, v in counts.items()} if counts else {}
        self.nested = 0.0
        self._t0: Optional[float] = None
        self._ann = None

    def add(self, name: str, v: float = 1.0) -> None:
        self.counts[name] = self.counts.get(name, 0.0) + float(v)

    def __enter__(self) -> "timed":
        if _state.enabled:
            self._ann = _annotation(self.name)
            _state.timed_stack().append(self)
            self._t0 = clock.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        if self._t0 is None:
            return False
        dt = clock.monotonic() - self._t0
        st = _state.timed_stack()
        if st and st[-1] is self:
            st.pop()
            if st:
                st[-1].nested += dt
        _metrics.inc(self.name + "_seconds", dt - self.nested)
        for k, v in self.counts.items():
            _metrics.inc(k, v)
        if self._ann is not None:
            if self.counts:
                self._ann.set_metadata(**self.counts)
            self._ann.__exit__(None, None, None)
        return False


def record_span_dict(d: Optional[Dict[str, Any]]) -> None:
    """Record a pre-built span dict (e.g. one shipped back over worker
    IPC)."""
    if d:
        _state.record_dict(d)


# ----------------------------------------------------------------------
# Trace merge + export
# ----------------------------------------------------------------------
def read_spills(obs_dir: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    if not os.path.isdir(obs_dir):
        return out
    for fn in sorted(os.listdir(obs_dir)):
        if not (fn.startswith("spans-") and fn.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(obs_dir, fn), "rb") as f:
                for raw in f:
                    raw = raw.strip()
                    if not raw:
                        continue
                    try:
                        out.append(json_loads(raw))
                    except ValueError:
                        continue  # torn tail line from a killed process
        except OSError:
            continue
    return out


def merge_spans(spans: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Dedupe by span_id (last-writer wins after a deterministic sort) and
    return spans ordered by (t0, span_id) — the merge that makes one trace
    out of failover re-executions."""
    best: Dict[str, Dict[str, Any]] = {}
    for s in sorted(spans, key=lambda s: (s.get("t0", 0.0), s.get("dur", 0.0))):
        sid = s.get("span_id")
        if sid:
            best[sid] = s
    return sorted(best.values(), key=lambda s: (s.get("t0", 0.0), s["span_id"]))


def merge_trace(obs_dir: str, trace_id: str,
                extra_spans: Optional[Iterable[Dict[str, Any]]] = None
                ) -> List[Dict[str, Any]]:
    spans = [s for s in read_spills(obs_dir) if s.get("trace_id") == trace_id]
    if extra_spans:
        spans.extend(s for s in extra_spans if s.get("trace_id") == trace_id)
    return merge_spans(spans)


def span_tree(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Roots / children / orphans view (orphan = non-root span whose
    parent_id is absent from the set) — what the failover test asserts."""
    by_id = {s["span_id"]: s for s in spans}
    children: Dict[str, List[str]] = {}
    roots, orphans = [], []
    for s in spans:
        pid = s.get("parent_id")
        if pid is None:
            roots.append(s["span_id"])
        elif pid in by_id:
            children.setdefault(pid, []).append(s["span_id"])
        else:
            orphans.append(s["span_id"])
    return {"roots": roots, "children": children, "orphans": orphans,
            "by_id": by_id}


def chrome_trace(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Catapult (chrome://tracing / Perfetto) JSON: complete "X" events
    with µs timestamps, plus process-name metadata."""
    events: List[Dict[str, Any]] = []
    pids = {}
    for s in spans:
        pid = s.get("pid", 0)
        if pid not in pids:
            pids[pid] = True
            events.append({
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": f"dj-pid-{pid}"},
            })
        args = dict(s.get("attrs") or {})
        args["span_id"] = s["span_id"]
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        args["trace_id"] = s.get("trace_id")
        events.append({
            "ph": "X",
            "name": s.get("name", "span"),
            "cat": s.get("kind", "span"),
            "ts": s.get("t0", 0.0) * 1e6,
            "dur": max(s.get("dur", 0.0), 1e-6) * 1e6,
            "pid": pid,
            "tid": s.get("tid", 0),
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ----------------------------------------------------------------------
# Metrics
# ----------------------------------------------------------------------
class MetricsRegistry:
    """Bounded named counters / gauges / fixed-bucket histograms.

    Thread-safe; past MAX_METRICS distinct names new metrics are counted
    in ``dropped`` instead of growing without bound. ``snapshot()`` is the
    JSON-safe wire shape and ``merge()`` folds many snapshots (one per
    process) into one."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, List[Any]] = {}  # [counts, sum, count]
        self.dropped = 0

    def _room(self, store: Dict[str, Any], name: str) -> bool:
        if name in store:
            return True
        total = len(self._counters) + len(self._gauges) + len(self._hists)
        if total >= MAX_METRICS:
            self.dropped += 1
            return False
        return True

    def inc(self, name: str, v: float = 1.0) -> None:
        if not _state.enabled:
            return
        with self._lock:
            if self._room(self._counters, name):
                self._counters[name] = self._counters.get(name, 0.0) + v

    def gauge(self, name: str, v: float) -> None:
        if not _state.enabled:
            return
        with self._lock:
            if self._room(self._gauges, name):
                self._gauges[name] = float(v)

    def gauge_max(self, name: str, v: float) -> None:
        if not _state.enabled:
            return
        with self._lock:
            if self._room(self._gauges, name):
                self._gauges[name] = max(self._gauges.get(name, v), float(v))

    def observe(self, name: str, v: float) -> None:
        """Record into a fixed-bucket seconds histogram."""
        if not _state.enabled:
            return
        with self._lock:
            if not self._room(self._hists, name):
                return
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = [[0] * len(SECONDS_BUCKETS), 0.0, 0]
            counts, _, _ = h
            for i, edge in enumerate(SECONDS_BUCKETS):
                if v <= edge:
                    counts[i] += 1
                    break
            h[1] += v
            h[2] += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {
                    n: {"buckets": list(SECONDS_BUCKETS[:-1]) + ["inf"],
                        "counts": list(h[0]), "sum": h[1], "count": h[2]}
                    for n, h in self._hists.items()
                },
                "dropped": self.dropped,
                "pid": os.getpid(),
            }

    def flush(self, path: str) -> None:
        """Atomically write this process's snapshot to ``path``."""
        snap = self.snapshot()
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(json_dumps(snap))
        os.replace(tmp, path)

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self.dropped = 0

    @staticmethod
    def merge(snaps: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        hists: Dict[str, Dict[str, Any]] = {}
        dropped = 0
        for s in snaps:
            for k, v in (s.get("counters") or {}).items():
                counters[k] = counters.get(k, 0.0) + v
            for k, v in (s.get("gauges") or {}).items():
                gauges[k] = max(gauges.get(k, v), v)  # gauges merge as max
            for k, h in (s.get("histograms") or {}).items():
                agg = hists.setdefault(k, {
                    "buckets": h.get("buckets"),
                    "counts": [0] * len(h.get("counts") or []),
                    "sum": 0.0, "count": 0})
                for i, c in enumerate(h.get("counts") or []):
                    agg["counts"][i] += c
                agg["sum"] += h.get("sum", 0.0)
                agg["count"] += h.get("count", 0)
            dropped += s.get("dropped", 0)
        return {"counters": counters, "gauges": gauges,
                "histograms": hists, "dropped": dropped}


_metrics = MetricsRegistry()


def metrics() -> MetricsRegistry:
    return _metrics


def metrics_spill_path(obs_dir: str) -> str:
    return os.path.join(obs_dir, f"metrics-{os.getpid()}.json")


def flush_metrics(obs_dir: str) -> None:
    os.makedirs(obs_dir, exist_ok=True)
    _metrics.flush(metrics_spill_path(obs_dir))


def merged_metrics(obs_dir: str) -> Dict[str, Any]:
    """Fold every per-process metrics spill in ``obs_dir`` together (plus
    the live in-process registry)."""
    snaps = [_metrics.snapshot()]
    if os.path.isdir(obs_dir):
        for fn in sorted(os.listdir(obs_dir)):
            if fn.startswith("metrics-") and fn.endswith(".json"):
                try:
                    with open(os.path.join(obs_dir, fn), "rb") as f:
                        snaps.append(json_loads(f.read()))
                except (OSError, ValueError):
                    continue
    return MetricsRegistry.merge(snaps)


def histogram_percentile(hist: Dict[str, Any], q: float) -> float:
    """Percentile estimate from a fixed-bucket histogram (upper-edge
    rule)."""
    counts = hist.get("counts") or []
    total = hist.get("count", 0)
    if not total:
        return 0.0
    rank = q * total
    seen = 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= rank:
            edge = SECONDS_BUCKETS[i]
            return edge if edge != float("inf") else SECONDS_BUCKETS[-2]
    return SECONDS_BUCKETS[-2]
