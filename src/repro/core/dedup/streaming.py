"""Streaming incremental MinHash-LSH deduplication (paper §E.1 + §E.3).

Turns the MinHash ``Deduplicator`` from a pipeline *barrier* into a stateful
pipeline *stage*: blocks flow through, signatures are computed in
device-sized super-batches, candidate pairs are found by incremental
hash-based band aggregation (no sort/shuffle), and a growable union-find
decides keep/drop online — so a recipe containing dedup keeps the streaming
executor's block pipelining and bounded-memory guarantee.

Three components, composed by :class:`StreamingMinHashState`:

* :class:`SignatureBatcher` — accumulates shingled docs across blocks into
  super-batches and dispatches the existing ``repro.kernels.minhash`` Pallas
  kernel once per batch instead of once per block (bucketed pad shapes keep
  the compile cache bounded) — the ShardedEngine super-batching pattern
  applied to dedup; the host path keeps the cache-resident per-doc loop.
  When a pipelineable chain precedes the stage, signatures are instead
  precomputed worker-side (``presign_ops`` plants an internal
  ``minhash_signature_mapper`` on that chain's dispatch), overlapping the
  embarrassingly-parallel compute with driver-side indexing.
* :class:`LSHBandIndex` — incremental band-hash -> bucket-head registry
  (hash aggregation, paper §E.1). Shingle payloads for bucket heads — the
  dominant memory term, needed only for Jaccard verification — spill to an
  append-only disk file beyond a resident budget, so resident memory is
  O(band index), not O(dataset).
* :class:`StreamingUnionFind` — growable union-find with keep-first
  bookkeeping.

Semantics vs. the exact barriered result (``minhash_dedup_indices``):

* **keep-first** (single pass): doc *i* is kept iff no earlier doc is
  connected to it *at the time i arrives*. Candidate pairs always point
  backwards (bucket head index < doc index), so the exact keep set is a
  subset of the keep-first keep set: if *i* is the minimum of its final
  component it is also the minimum of its at-time component (which only
  contains docs <= i from the same final component). Keep-first may
  additionally keep docs whose components merge only *retroactively*
  (a later doc bridging two already-emitted components). This containment
  relation is property-tested in ``tests/test_streaming_dedup.py``.
* **windowed** (``windowed=True``): keep-first with a bounded
  retroactive-merge horizon — each doc's keep/drop decision is deferred
  until ``window`` newer docs have arrived, so merges bridged within the
  horizon are honored. Component minima only decrease over time, so the
  keep sets nest: ``exact ⊆ windowed ⊆ keep_first`` (``window=0``
  degenerates to keep_first; ``window=∞`` would be exact), memory stays
  O(index + window), and latency stays bounded. Property-tested against
  both oracles.
* **exact** (two passes, ``exact=True``): pass 1 streams blocks through,
  building the full verified candidate-pair registry in the barriered
  path's band-major order while spilling the samples to a disk file; the
  finalize pass replays the spill with the *final* components, reproducing
  ``minhash_dedup_indices`` (same union-find backend, same pair order, same
  component ids) — byte-identical output, still O(index + one block)
  resident memory, at the cost of one disk round-trip.
"""
from __future__ import annotations

import os
import tempfile
import time
from collections import OrderedDict, deque
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from repro.core import obs
from repro.core.dedup.block_shingle import block_shingle_hashes
from repro.core.dedup.minhash import (
    jaccard_unique, lsh_bands, make_permutations, shingle_hashes,
    signatures_batch_vectorized,
)

Sample = Dict[str, Any]

DEFAULT_SUPER_BATCH = 2048
DEFAULT_RESIDENT_SHINGLES = 50_000


# ---------------------------------------------------------------------------
# signature super-batching
# ---------------------------------------------------------------------------


class SignatureBatcher:
    """Accumulates shingled docs across blocks and computes MinHash
    signatures in one dispatch per super-batch.

    Per-doc signatures are independent, so batching composition never changes
    values — only how often the (vectorized numpy or Pallas) signature kernel
    is entered. ``add()`` buffers; ``ready`` flips once ``super_batch`` docs
    are pending; ``flush()`` returns ``(payloads, docs, sigs)`` for
    everything buffered.
    """

    def __init__(self, n_perm: int = 128, ngram: int = 5, seed: int = 42,
                 use_kernel: bool = False, super_batch: int = DEFAULT_SUPER_BATCH):
        self.n_perm = n_perm
        self.ngram = ngram
        self.use_kernel = use_kernel
        self.super_batch = max(1, super_batch)
        self._a, self._b = make_permutations(n_perm, seed)
        self._docs: List[np.ndarray] = []
        self._payloads: List[Any] = []
        self.docs_in = 0
        self.dispatches = 0

    def add(self, text: str, payload: Any = None) -> None:
        self._docs.append(shingle_hashes(text, n=self.ngram))
        self._payloads.append(payload)
        self.docs_in += 1

    def add_block(self, texts: List[str], payloads: List[Any],
                  utf8: Optional[Tuple[np.ndarray, bytes]] = None) -> None:
        """``add`` for every document of an input block. On the kernel path
        the whole block is shingled at once and its n-grams hashed on the
        device (``block_shingle_hashes``; ``utf8`` is the block's text
        column as ``(offsets, buffer)`` where it has one), with the same
        values as ``add``."""
        if not self.use_kernel:
            for t, p in zip(texts, payloads):
                self.add(t, p)
            return
        self._docs.extend(block_shingle_hashes(texts, n=self.ngram, utf8=utf8))
        self._payloads.extend(payloads)
        self.docs_in += len(texts)

    @property
    def pending(self) -> int:
        return len(self._docs)

    @property
    def ready(self) -> bool:
        return len(self._docs) >= self.super_batch

    def flush(self) -> Tuple[List[Any], List[np.ndarray], np.ndarray]:
        """One signature dispatch for every buffered doc."""
        docs, payloads = self._docs, self._payloads
        self._docs, self._payloads = [], []
        if not docs:
            return [], [], np.zeros((0, self.n_perm), dtype=np.uint32)
        with obs.timed("dedup.sign"):
            sigs = self._sign(docs)
        return payloads, docs, sigs

    def _sign(self, docs: List[np.ndarray]) -> np.ndarray:
        self.dispatches += 1
        # kernel-batch span: flush runs driver-side, so the ambient parent
        # is the enclosing run/segment span and timing comes straight from
        # the injectable clock (docs/observability.md)
        cur = obs.current_span()
        kb = obs.start_span(cur.trace_id if cur else None, "kernel:minhash",
                            kind="kernel_batch",
                            parent_id=cur.span_id if cur else None)
        m = obs.metrics()
        m.inc("dedup.signature_dispatches_total")
        m.inc("dedup.signature_docs_total", len(docs))
        if self.use_kernel:
            from repro.kernels.minhash.ops import minhash_signatures_packed

            # packed-ragged dispatch: one vectorized scatter builds the
            # dense layout instead of a per-doc pad loop (bit-exact)
            lens = np.fromiter((d.size for d in docs), np.int64, len(docs))
            offsets = np.zeros(len(docs) + 1, np.int64)
            np.cumsum(lens, out=offsets[1:])
            values = np.concatenate(docs) if len(docs) else np.zeros(0, np.uint64)
            sigs = np.asarray(minhash_signatures_packed(
                values, offsets, self._a, self._b))
        else:
            from repro.core.dedup.minhash import signature_ref

            # per-doc reference loop: cache-resident (128, S) intermediates
            # beat padded super-batch arrays on the host (numpy's scalar
            # uint64 % is fast; DRAM traffic is not) — the super-batch win
            # on the host path is dispatch amortization for the KERNEL
            # branch above and presign offload, not host vectorization
            sigs = np.empty((len(docs), self.n_perm), dtype=np.uint32)
            for i, d in enumerate(docs):
                sigs[i] = signature_ref(d, self._a, self._b)
        if kb is not None:
            kb.set(docs=len(docs), kernel=self.use_kernel).end()
        return sigs


# ---------------------------------------------------------------------------
# spillable shingle store
# ---------------------------------------------------------------------------


class ShingleStore:
    """doc id -> uint64 shingle array with a bounded resident set.

    Entries past ``max_resident`` spill (LRU) to an append-only binary file;
    the in-memory side keeps only an ``id -> (offset, count)`` index. Arrays
    are immutable, so a re-loaded entry never has to be re-written.
    """

    def __init__(self, spill_dir: Optional[str] = None,
                 max_resident: int = DEFAULT_RESIDENT_SHINGLES):
        self.max_resident = max(1, max_resident)
        self._hot: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self._offsets: Dict[int, Tuple[int, int]] = {}
        self._spill_dir = spill_dir
        self._path: Optional[str] = None
        self._write_fh = None
        self._read_fh = None
        self._write_pos = 0
        self.spilled = 0
        self.reloads = 0

    def _ensure_file(self) -> None:
        if self._write_fh is None:
            os.makedirs(self._spill_dir, exist_ok=True) if self._spill_dir else None
            fd, self._path = tempfile.mkstemp(
                prefix="dj-shingles-", suffix=".bin", dir=self._spill_dir)
            self._write_fh = os.fdopen(fd, "wb")

    def put(self, doc_id: int, arr: np.ndarray) -> None:
        self._hot[doc_id] = arr
        self._hot.move_to_end(doc_id)
        while len(self._hot) > self.max_resident:
            victim, varr = self._hot.popitem(last=False)
            if victim not in self._offsets:  # write-once
                self._ensure_file()
                raw = np.ascontiguousarray(varr, dtype=np.uint64).tobytes()
                self._write_fh.write(raw)
                self._offsets[victim] = (self._write_pos, varr.size)
                self._write_pos += len(raw)
                self.spilled += 1

    def get(self, doc_id: int) -> np.ndarray:
        arr = self._hot.get(doc_id)
        if arr is not None:
            self._hot.move_to_end(doc_id)
            return arr
        off, count = self._offsets[doc_id]  # KeyError = caller bug
        self._write_fh.flush()
        if self._read_fh is None:
            self._read_fh = open(self._path, "rb")
        self._read_fh.seek(off)
        arr = np.frombuffer(self._read_fh.read(count * 8), dtype=np.uint64)
        self.reloads += 1
        self.put(doc_id, arr)
        return arr

    def __contains__(self, doc_id: int) -> bool:
        return doc_id in self._hot or doc_id in self._offsets

    def __len__(self) -> int:
        return len(self._hot) + sum(1 for k in self._offsets if k not in self._hot)

    def close(self) -> None:
        for fh in (self._write_fh, self._read_fh):
            if fh is not None:
                try:
                    fh.close()
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
        self._write_fh = self._read_fh = None
        if self._path:
            try:
                os.remove(self._path)
            except OSError:
                pass
            self._path = None


# ---------------------------------------------------------------------------
# incremental LSH band index
# ---------------------------------------------------------------------------


class LSHBandIndex:
    """Incremental band-hash -> bucket-head registry (hash aggregation).

    ``insert`` reproduces ``candidate_pairs_hash_agg``'s star-edge structure
    exactly: the bucket head for a ``(band, key)`` is the first doc inserted
    with that key, so inserting docs in index order yields the identical
    candidate-pair *set* as the barriered batch pass. The resident core is
    the key->head int maps (O(index)); shingle payloads — needed only for
    Jaccard verification and only for bucket heads — live in a spillable
    :class:`ShingleStore`.
    """

    def __init__(self, n_bands: int, spill_dir: Optional[str] = None,
                 max_resident_shingles: int = DEFAULT_RESIDENT_SHINGLES):
        self.n_bands = n_bands
        self._buckets: List[Dict[int, int]] = [dict() for _ in range(n_bands)]
        self.shingles = ShingleStore(spill_dir, max_resident_shingles)
        self.n_docs = 0

    def insert(self, doc_id: int, band_keys: np.ndarray,
               doc_hashes: np.ndarray) -> List[Tuple[int, int, int]]:
        """Register one doc; returns ``(band, head, doc_id)`` candidate
        edges against existing bucket heads (may repeat a head across
        bands, matching the barriered pair stream)."""
        pairs: List[Tuple[int, int, int]] = []
        created = False
        for band in range(self.n_bands):
            bucket = self._buckets[band]
            key = int(band_keys[band])
            head = bucket.get(key)
            if head is None:
                bucket[key] = doc_id
                created = True
            else:
                pairs.append((band, head, doc_id))
        if created:
            # only bucket heads can appear as a future pair's left endpoint
            self.shingles.put(doc_id, doc_hashes)
        self.n_docs += 1
        return pairs

    @property
    def n_buckets(self) -> int:
        return sum(len(b) for b in self._buckets)

    def close(self) -> None:
        self.shingles.close()


# ---------------------------------------------------------------------------
# growable keep-first union-find
# ---------------------------------------------------------------------------


class StreamingUnionFind:
    """Union-by-rank + path-halving over a growable id space, tracking each
    component's minimum member — the keep-first representative."""

    def __init__(self):
        self._parent: Dict[int, int] = {}
        self._rank: Dict[int, int] = {}
        self._min: Dict[int, int] = {}

    def add(self, x: int) -> None:
        if x not in self._parent:
            self._parent[x] = x
            self._rank[x] = 0
            self._min[x] = x

    def find(self, x: int) -> int:
        p = self._parent
        while p[x] != x:
            p[x] = p[p[x]]  # path halving
            x = p[x]
        return x

    def union(self, a: int, b: int) -> bool:
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        if self._rank[ra] < self._rank[rb]:
            ra, rb = rb, ra
        self._parent[rb] = ra
        self._min[ra] = min(self._min[ra], self._min[rb])
        if self._rank[ra] == self._rank[rb]:
            self._rank[ra] += 1
        return True

    def connected(self, a: int, b: int) -> bool:
        return self.find(a) == self.find(b)

    def component_min(self, x: int) -> int:
        """First-arrived member of x's component (the kept representative)."""
        return self._min[self.find(x)]

    def __len__(self) -> int:
        return len(self._parent)


# ---------------------------------------------------------------------------
# the streaming dedup stage
# ---------------------------------------------------------------------------


class StreamingMinHashState:
    """Stateful stream stage: consumes upstream SampleBlocks, emits deduped
    SampleBlocks (see module docstring for keep-first vs exact semantics).

    Driven by ``dataset.iter_stream_blocks`` through :meth:`stream_blocks`;
    all heavyweight state (band index, union-find, spill files) lives for
    exactly one segment traversal and is released in ``close()``.
    """

    def __init__(self, *, n_perm: int = 128, n_bands: int = 16, ngram: int = 5,
                 jaccard_threshold: float = 0.7, verify_jaccard: bool = True,
                 backend: str = "balanced", n_partitions: int = 8,
                 use_kernel: bool = False, seed: int = 42, exact: bool = False,
                 windowed: bool = False, window: int = 4096,
                 super_batch: int = DEFAULT_SUPER_BATCH,
                 spill_dir: Optional[str] = None,
                 max_resident_shingles: int = DEFAULT_RESIDENT_SHINGLES):
        if n_perm % n_bands:
            raise ValueError(f"n_perm ({n_perm}) must divide into n_bands ({n_bands})")
        self.n_perm = n_perm
        self.n_bands = n_bands
        self.ngram = ngram
        self.seed = seed
        self.use_kernel = use_kernel
        self.jaccard_threshold = jaccard_threshold
        self.verify = verify_jaccard and jaccard_threshold > 0
        self.backend = backend
        self.n_partitions = n_partitions
        self.exact = exact
        self.windowed = bool(windowed) and not exact
        self.window = max(0, int(window))
        # (gid, sample) pairs whose keep/drop decision is still deferred
        self._window_q: "deque[Tuple[int, Sample]]" = deque()
        self.batcher = SignatureBatcher(n_perm=n_perm, ngram=ngram, seed=seed,
                                        use_kernel=use_kernel, super_batch=super_batch)
        self.index = LSHBandIndex(n_bands, spill_dir=spill_dir,
                                  max_resident_shingles=max_resident_shingles)
        self.uf = StreamingUnionFind()
        self.n_seen = 0
        self.n_kept = 0
        self.n_pairs = 0
        self.n_verified = 0
        # exact mode: verified pairs in the barriered band-major order + the
        # sample spill (disk, not memory)
        self._pairs_by_band: List[List[Tuple[int, int]]] = [[] for _ in range(n_bands)]
        self._spill_dir = spill_dir
        self._spill_path: Optional[str] = None
        self._spill_fh = None

    # -- exact-mode sample spill ------------------------------------------
    def _ensure_spill(self) -> None:
        if self._spill_fh is None:
            if self._spill_dir:
                os.makedirs(self._spill_dir, exist_ok=True)
            fd, self._spill_path = tempfile.mkstemp(
                prefix="dj-dedup-spill-", suffix=".jsonl", dir=self._spill_dir)
            self._spill_fh = os.fdopen(fd, "wb")

    def _spill_samples(self, samples: List[Sample]) -> None:
        from repro.core.storage import json_dumps

        self._ensure_spill()
        for s in samples:
            self._spill_fh.write(json_dumps(s) + b"\n")

    def _spill_lines(self, lines: Iterable[bytes]) -> None:
        """Spill pre-serialized JSONL lines (a ColumnBlock's export codec) —
        byte-identical to ``_spill_samples`` on the decoded rows, without
        ever building the row dicts."""
        self._ensure_spill()
        for raw in lines:
            self._spill_fh.write(raw + b"\n")

    def _replay_spill(self) -> Iterator[Sample]:
        from repro.core.storage import read_jsonl

        if self._spill_path is None:
            return iter(())
        self._spill_fh.flush()
        return read_jsonl(self._spill_path)

    # -- worker-side signature precompute ----------------------------------
    def presign_ops(self) -> Optional[List[Any]]:
        """Ops the engine should run over the upstream block stream BEFORE
        this stage (``dataset.iter_stream_blocks`` dispatches them through
        ``engine.map_block_chain``): shingle + signature per sample, i.e. the
        embarrassingly-parallel bulk of dedup compute, pipelined across
        worker processes and overlapped with driver-side band indexing.
        ``None`` on the kernel path — there the driver-side SignatureBatcher
        owns dispatch so super-batches hit the Pallas kernel with bucketed
        shapes."""
        if self.use_kernel:
            return None
        from repro.core.registry import create_op

        return [create_op({
            "name": "minhash_signature_mapper", "num_permutations": self.n_perm,
            "ngram": self.ngram, "seed": self.seed})]

    def _take_presigned(self, samples: List[Sample]
                        ) -> Tuple[List[Sample], List[np.ndarray], np.ndarray]:
        """Strip worker-computed signature carriers off a pre-signed block
        (computing any stragglers — e.g. fault-tolerance replacements —
        on the driver), preserving arrival order."""
        from repro.ops.dedup_ops import MH_DOC_KEY, MH_SIG_KEY

        docs: List[np.ndarray] = []
        sigs: List[np.ndarray] = []
        for s in samples:
            d = s.pop(MH_DOC_KEY, None)
            g = s.pop(MH_SIG_KEY, None)
            if d is None or g is None:
                d = shingle_hashes(s.get("text", ""), n=self.ngram)
                g = signatures_batch_vectorized([d], self.batcher._a,
                                                self.batcher._b)[0]
            docs.append(d)
            sigs.append(g)
        payloads: List[Sample] = [None] * len(samples) if self.exact \
            else list(samples)
        sig_arr = np.stack(sigs) if sigs else \
            np.zeros((0, self.n_perm), dtype=np.uint32)
        return payloads, docs, sig_arr

    def _take_presigned_columns(self, block
                                ) -> Tuple[List[Sample], List[np.ndarray], np.ndarray]:
        """Columnar counterpart of :meth:`_take_presigned`: read the
        signature carriers straight off a ColumnBlock's py columns — no row
        dicts. Exact mode only (payloads are all ``None``; emission happens
        from the spill replay, never from these samples)."""
        from repro.ops.dedup_ops import MH_DOC_KEY, MH_SIG_KEY

        docs_c = block.column_values(MH_DOC_KEY)
        sigs_c = block.column_values(MH_SIG_KEY)
        texts = None
        docs: List[np.ndarray] = []
        sigs: List[np.ndarray] = []
        for i in range(len(block)):
            d, g = docs_c[i], sigs_c[i]
            if d is None or g is None:
                # straggler (e.g. fault-tolerance replacement row): recompute
                if texts is None:
                    texts = block.string_values("text")
                d = shingle_hashes(texts[i], n=self.ngram)
                g = signatures_batch_vectorized([d], self.batcher._a,
                                                self.batcher._b)[0]
            docs.append(d)
            sigs.append(g)
        sig_arr = np.stack(sigs) if sigs else \
            np.zeros((0, self.n_perm), dtype=np.uint32)
        return [None] * len(block), docs, sig_arr

    # -- per-doc ingestion -------------------------------------------------
    def _ingest(self, payloads: List[Sample], docs: List[np.ndarray],
                sigs: np.ndarray) -> List[Sample]:
        """Insert a flushed super-batch into the index; returns keep-first
        survivors (empty in exact mode, which defers all emission)."""
        if sigs.shape[0] == 0:
            return []
        with obs.timed("dedup.index"):
            return self._index(payloads, docs, sigs)

    def _index(self, payloads: List[Sample], docs: List[np.ndarray],
               sigs: np.ndarray) -> List[Sample]:
        kept: List[Sample] = []
        keys = lsh_bands(sigs, self.n_bands)
        for j, sample in enumerate(payloads):
            gid = self.n_seen
            self.n_seen += 1
            self.uf.add(gid)
            # uniqued shingles: lossless for Jaccard (set semantics), enables
            # the sorted-merge verifier, and halves spill/IPC bytes. The
            # signature was already computed from the raw array upstream.
            du = np.unique(docs[j])
            edges = self.index.insert(gid, keys[j], du)
            self.n_pairs += len(edges)
            for band, head, _ in edges:
                ok = True
                if self.verify:
                    with obs.timed("dedup.verify",
                                   {"dedup.candidates_total": 1}) as vt:
                        ok = jaccard_unique(self.index.shingles.get(head), du) \
                            >= self.jaccard_threshold
                        vt.add("dedup.duplicates_total", ok)
                    self.n_verified += 1
                if not ok:
                    continue
                if self.exact:
                    self._pairs_by_band[band].append((head, gid))
                self.uf.union(head, gid)
            if self.exact:
                continue
            if self.windowed:
                # defer the decision until `window` newer docs have arrived
                self._window_q.append((gid, sample))
            elif self.uf.component_min(gid) == gid:
                # keep-first: gid is its component's first member right now
                sample.setdefault("stats", {})["dup_component"] = gid
                kept.append(sample)
                self.n_kept += 1
        if self.windowed:
            kept.extend(self._drain_window(self.window))
        return kept

    def _drain_window(self, target: int) -> List[Sample]:
        """Emit every deferred doc beyond ``target`` pending entries that is
        STILL its component's minimum — merges bridged while it waited in
        the horizon demote it, which plain keep-first would have missed."""
        out: List[Sample] = []
        while len(self._window_q) > target:
            gid, sample = self._window_q.popleft()
            if self.uf.component_min(gid) == gid:
                sample.setdefault("stats", {})["dup_component"] = gid
                out.append(sample)
                self.n_kept += 1
        return out

    # -- the stage driver --------------------------------------------------
    def stream_blocks(self, blocks: Iterable, check_cancel=None
                      ) -> Iterator[Tuple[Any, dict]]:
        """Drive the upstream block iterator through the dedup stage,
        yielding ``(SampleBlock, stats)`` as super-batches flush. Exact mode
        spills pass-1 samples to disk and emits everything from
        :meth:`_finalize_exact` once upstream is exhausted."""
        from repro.core.storage import SampleBlock

        from repro.ops.dedup_ops import MH_DOC_KEY, MH_SIG_KEY

        try:
            for blk in blocks:
                if check_cancel is not None:
                    check_cancel()
                t0 = time.perf_counter()
                n_in = len(blk)
                out: List[Sample] = []
                # non-materialized ColumnBlocks expose schema + columns
                # without decoding row dicts; anything else uses .samples
                cb = blk if (hasattr(blk, "has_column")
                             and not blk.materialized) else None
                presigned = (cb.has_column(MH_DOC_KEY) if cb is not None
                             else bool(blk.samples and MH_DOC_KEY in blk.samples[0]))
                if presigned:
                    # worker-pre-signed block: flush any batcher backlog
                    # first (doc ids must follow arrival order), then ingest
                    # directly — nothing left to super-batch
                    if self.batcher.pending:
                        out.extend(self._ingest(*self.batcher.flush()))
                    if cb is not None and self.exact:
                        # zero-materialization path: spill the export codec's
                        # lines minus the carrier keys, read the carriers
                        # straight off the py columns
                        self._spill_lines(cb.iter_json_lines(
                            exclude=(MH_DOC_KEY, MH_SIG_KEY)))
                        out.extend(self._ingest(*self._take_presigned_columns(cb)))
                    else:
                        # keep-first emission needs the row dicts as payloads
                        payloads, docs, sigs = self._take_presigned(blk.samples)
                        if self.exact:
                            self._spill_samples(blk.samples)
                        out.extend(self._ingest(payloads, docs, sigs))
                else:
                    texts = utf8 = None
                    if cb is not None and self.exact and "py" not in cb.kinds:
                        # validate the text column BEFORE spilling so a
                        # fallback can never double-spill the block
                        try:
                            texts = cb.string_values("text")
                        except (TypeError, ValueError):
                            texts = None
                    if cb is not None and self.use_kernel:
                        try:  # the block shingler reads the column's bytes
                            utf8 = cb.str_column("text")
                        except TypeError:
                            pass
                    if texts is not None:
                        self._spill_lines(cb.iter_json_lines())
                        with obs.timed("dedup.shingle"):
                            self.batcher.add_block(texts, [None] * len(texts),
                                                   utf8=utf8)
                    else:
                        if self.exact:
                            self._spill_samples(blk.samples)
                        with obs.timed("dedup.shingle"):
                            samples = blk.samples
                            self.batcher.add_block(
                                [s.get("text", "") for s in samples],
                                [None] * len(samples) if self.exact else samples,
                                utf8=utf8)
                    while self.batcher.ready:
                        out.extend(self._ingest(*self.batcher.flush()))
                dt = time.perf_counter() - t0
                stats = {"op": "", "seconds": dt, "in": n_in,
                         "out": len(out), "errors": 0}
                if out or not self.exact:
                    yield SampleBlock(out, nbytes=0), stats
                elif n_in:  # exact pass 1: account ingestion, emit nothing
                    yield SampleBlock([], nbytes=0), stats

            # upstream exhausted: flush the tail, then finalize
            t0 = time.perf_counter()
            tail = self._ingest(*self.batcher.flush())
            if self.windowed:
                tail = tail + self._drain_window(0)
            if self.exact:
                if check_cancel is not None:
                    check_cancel()
                for out_blk in self._finalize_exact():
                    dt, t0 = time.perf_counter() - t0, time.perf_counter()
                    yield out_blk, {"op": "", "seconds": dt, "in": 0,
                                    "out": len(out_blk), "errors": 0}
                    if check_cancel is not None:
                        check_cancel()
            elif tail:
                yield SampleBlock(tail, nbytes=0), {
                    "op": "", "seconds": time.perf_counter() - t0, "in": 0,
                    "out": len(tail), "errors": 0}
        finally:
            self.close()

    def _finalize_exact(self) -> Iterator[Any]:
        """Replay the spill with the FINAL components, reproducing the
        barriered ``minhash_dedup_indices`` result exactly: same verified
        pairs in the same band-major order, same union-find backend, same
        component ids, keep = first member per component in index order."""
        from repro.core.dedup.unionfind import naive_components, partitioned_union
        from repro.core.storage import SampleBlock

        n = self.n_seen
        pairs = [p for band in self._pairs_by_band for p in band]
        if self.backend == "naive":
            comp = naive_components(n, pairs)
        else:
            comp = partitioned_union(n, pairs, n_partitions=self.n_partitions).components()
        seen: Dict[int, bool] = {}
        out: List[Sample] = []
        emit_every = max(1, self.batcher.super_batch)
        for i, s in enumerate(self._replay_spill()):
            c = int(comp[i])
            if c not in seen:
                seen[c] = True
                s.setdefault("stats", {})["dup_component"] = c
                out.append(s)
                self.n_kept += 1
                if len(out) >= emit_every:
                    yield SampleBlock(out, nbytes=0)
                    out = []
        if out:
            yield SampleBlock(out, nbytes=0)

    # -- bookkeeping -------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        return {
            "mode": ("exact" if self.exact
                     else "windowed" if self.windowed else "keep_first"),
            "n_seen": self.n_seen, "n_kept": self.n_kept,
            "n_pairs": self.n_pairs, "n_verified": self.n_verified,
            "n_buckets": self.index.n_buckets,
            "sig_dispatches": self.batcher.dispatches,
            "shingles_resident": len(self.index.shingles._hot),
            "shingles_spilled": self.index.shingles.spilled,
        }

    def close(self) -> None:
        self.index.close()
        for fh in (self._spill_fh,):
            if fh is not None:
                try:
                    fh.close()
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
        self._spill_fh = None
        if self._spill_path:
            try:
                os.remove(self._spill_path)
            except OSError:
                pass
            self._spill_path = None
