"""Columnar sample storage + streaming I/O + subset pre-splitting.

``SampleBlock`` is an Arrow-like unit: a list of sample dicts with a byte
estimate. Datasets are lists of blocks, pre-split to ~128 MB (paper §E.3) and
aligned to the worker count — the paper measured 2-3x end-to-end speedups
from exactly this (Fig. 4f: peak network I/O 160 -> 60 MB/s).

JSONL (orjson when available, stdlib ``json`` otherwise) with optional zstd
compression; streaming readers never load the whole file.
"""
from __future__ import annotations

import io
import json as _stdlib_json
import os
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Union

try:
    import orjson as _orjson
except Exception:  # pragma: no cover — optional accelerator
    _orjson = None

try:
    import zstandard as zstd
except Exception:  # pragma: no cover
    zstd = None

DEFAULT_BLOCK_BYTES = 128 * 2**20


if _orjson is not None:

    def json_dumps(obj: Any, sort_keys: bool = False) -> bytes:
        """Compact JSON bytes via orjson when available, stdlib otherwise —
        the shared serializer for storage, checkpointing, recipes, server."""
        return _orjson.dumps(obj, option=_orjson.OPT_SORT_KEYS if sort_keys else 0)

    json_loads = _orjson.loads
else:
    # one encoder per flavor, reused across calls — json.dumps() builds a
    # fresh JSONEncoder every call, measurable at columnar-ingest call rates
    _enc = _stdlib_json.JSONEncoder(separators=(",", ":"), ensure_ascii=False).encode
    _enc_sorted = _stdlib_json.JSONEncoder(
        separators=(",", ":"), ensure_ascii=False, sort_keys=True).encode

    def json_dumps(obj: Any, sort_keys: bool = False) -> bytes:
        """Compact JSON bytes via orjson when available, stdlib otherwise —
        the shared serializer for storage, checkpointing, recipes, server."""
        return (_enc_sorted(obj) if sort_keys else _enc(obj)).encode("utf-8")

    json_loads = _stdlib_json.loads



def sample_nbytes(sample: Dict[str, Any]) -> int:
    # fast estimate; exact enough for block splitting
    return len(json_dumps(sample))


class SampleBlock:
    __slots__ = ("samples", "nbytes")

    def __init__(self, samples: Optional[List[Dict[str, Any]]] = None, nbytes: int = -1):
        self.samples = samples if samples is not None else []
        self.nbytes = nbytes if nbytes >= 0 else sum(sample_nbytes(s) for s in self.samples)

    def __len__(self):
        return len(self.samples)

    def append(self, s: Dict[str, Any], nb: Optional[int] = None):
        self.samples.append(s)
        self.nbytes += nb if nb is not None else sample_nbytes(s)


def split_blocks(
    samples: Iterable[Dict[str, Any]],
    block_bytes: int = DEFAULT_BLOCK_BYTES,
    n_workers: int = 1,
    total_hint_bytes: Optional[int] = None,
) -> List[SampleBlock]:
    """Adaptive subset splitting: target min(block_bytes, total/n_workers)
    so every worker gets at least one block (paper §E.3)."""
    return list(iter_sample_blocks(samples, block_bytes=block_bytes,
                                   n_workers=n_workers,
                                   total_hint_bytes=total_hint_bytes))


# ---------------------------------------------------------------------------
# JSONL I/O (streaming; optional .zst)
# ---------------------------------------------------------------------------


def _open_read(path: str):
    if path.endswith(".zst"):
        if zstd is None:
            raise RuntimeError("zstandard unavailable")
        fh = open(path, "rb")
        return io.TextIOWrapper(zstd.ZstdDecompressor().stream_reader(fh), encoding="utf-8")
    return open(path, "r", encoding="utf-8")


def read_jsonl(path: str, limit: Optional[int] = None) -> Iterator[Dict[str, Any]]:
    """Streaming JSONL reader — O(1) memory (paper §E.3 'streaming loading')."""
    n = 0
    with _open_read(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            yield json_loads(line)
            n += 1
            if limit is not None and n >= limit:
                return


def write_jsonl(path: str, samples: Iterable[Dict[str, Any]]) -> int:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    n = 0
    if path.endswith(".zst"):
        if zstd is None:
            raise RuntimeError("zstandard unavailable")
        with open(path, "wb") as fh:
            with zstd.ZstdCompressor().stream_writer(fh) as w:
                for s in samples:
                    w.write(json_dumps(s) + b"\n")
                    n += 1
    else:
        with open(path, "wb") as f:
            for s in samples:
                f.write(json_dumps(s) + b"\n")
                n += 1
    return n


# ---------------------------------------------------------------------------
# Streaming block source / sink / prefetch (paper §E.3 'streaming loading')
# ---------------------------------------------------------------------------


def _open_read_binary(path: str):
    if path.endswith(".zst"):
        if zstd is None:
            raise RuntimeError("zstandard unavailable")
        fh = open(path, "rb")
        return io.BufferedReader(zstd.ZstdDecompressor().stream_reader(fh))
    return open(path, "rb")


def _read_jsonl_sized(path: str, limit: Optional[int] = None,
                      row_range: Optional[tuple] = None) -> Iterator[tuple]:
    """Streaming (sample, nbytes) pairs — read in binary so the raw line
    length IS the (uncompressed) byte size; block sizing costs no
    re-serialization and no re-encoding of non-ASCII text.

    ``row_range=(lo, hi)`` scopes the stream to that half-open row window
    (how a shard task reads only its slice): rows before ``lo`` are skipped
    WITHOUT json-decoding, the iterator stops at ``hi``."""
    n = 0
    lo, hi = row_range if row_range else (0, None)
    idx = 0
    with _open_read_binary(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            i, idx = idx, idx + 1
            if i < lo:
                continue
            if hi is not None and i >= hi:
                return
            yield json_loads(line), len(line)
            n += 1
            if limit is not None and n >= limit:
                return


def iter_sample_blocks(
    source: Union[str, Iterable[Dict[str, Any]]],
    block_bytes: int = DEFAULT_BLOCK_BYTES,
    n_workers: int = 1,
    total_hint_bytes: Optional[int] = None,
    limit: Optional[int] = None,
    columnar: bool = False,
    row_range: Optional[tuple] = None,
) -> Iterator[SampleBlock]:
    """Lazy block source: stream samples (from a JSONL path or any sample
    iterable) into ~``block_bytes`` blocks, yielding each block as soon
    as it fills — O(one block) memory, never the whole dataset.

    With ``columnar`` each block is encoded into a struct-of-arrays
    ``ColumnBlock`` (``repro.core.columnar``) at ingest — JSONL becomes a
    pure import codec; rows that the encoder rejects fall back to a plain
    SampleBlock for that block only."""
    if isinstance(source, str):
        # .zst: getsize is the COMPRESSED size while per-line sizes are
        # uncompressed. Still use it as a conservative hint — it UNDERSTATES
        # the total, so the worker shrink at worst over-splits (more blocks
        # than workers keeps every worker busy), never under-splits to one
        # giant single-worker block.
        if total_hint_bytes is None:
            try:
                total_hint_bytes = os.path.getsize(source)
            except OSError:
                total_hint_bytes = None
        sized: Iterable[tuple] = _read_jsonl_sized(source, limit=limit,
                                                   row_range=row_range)
    else:
        sized = ((s, sample_nbytes(s)) for s in source)
        if row_range:
            import itertools

            sized = itertools.islice(sized, row_range[0], row_range[1])
    if total_hint_bytes and n_workers > 1:
        block_bytes = max(1, min(block_bytes, total_hint_bytes // n_workers))
    if columnar:
        from repro.core.columnar import ColumnBlock

        def encode(rows: List[Dict[str, Any]], nb: int):
            try:
                return ColumnBlock.from_samples(rows, nbytes=nb)
            except Exception:  # exotic rows: keep them, just not columnar
                return SampleBlock(rows, nbytes=nb)

        rows: List[Dict[str, Any]] = []
        acc = 0
        for s, nb in sized:
            if acc + nb > block_bytes and rows:
                yield encode(rows, acc)
                rows, acc = [], 0
            rows.append(s)
            acc += nb
        if rows:
            yield encode(rows, acc)
        return
    blk = SampleBlock()
    for s, nb in sized:
        if blk.nbytes + nb > block_bytes and len(blk):
            yield blk
            blk = SampleBlock()
        blk.append(s, nb)
    if len(blk):
        yield blk


def reservoir_sample(stream: Iterable[Any], k: int, seed: int = 0) -> List[Any]:
    """Uniform k-sample over a stream (Vitter's Algorithm R): O(k) memory,
    one pass, no full decode. Selected items are returned in first-seen
    order so downstream probing stays deterministic. Replaces the
    head-biased ``read_jsonl(limit=k)`` probe for streamed sources."""
    import random

    rng = random.Random(seed)
    sample: List[tuple] = []  # (stream_index, item)
    for i, item in enumerate(stream):
        if len(sample) < k:
            sample.append((i, item))
        else:
            j = rng.randrange(i + 1)
            if j < k:
                sample[j] = (i, item)
    sample.sort(key=lambda t: t[0])
    return [item for _, item in sample]


class BlockWriter:
    """Streaming block sink: appends blocks to one JSONL (optionally .zst)
    file as they arrive, holding at most one block in flight. Writes go to a
    ``.tmp`` sidecar published atomically on successful close, so a mid-run
    failure never clobbers a previous good export."""

    def __init__(self, path: str):
        import tempfile

        self.path = path
        if path.endswith(".zst") and zstd is None:
            raise RuntimeError("zstandard unavailable")
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self.n = 0
        # unique sidecar: concurrent runs exporting to the same path must not
        # truncate each other's in-flight tmp file
        fd, self._tmp = tempfile.mkstemp(
            prefix=os.path.basename(path) + ".", suffix=".tmp", dir=parent)
        # mkstemp's 0600 would stick after publish; match what open() under
        # the caller's umask would have created
        um = os.umask(0)
        os.umask(um)
        os.chmod(self._tmp, 0o666 & ~um)
        self._fh = os.fdopen(fd, "wb")
        if path.endswith(".zst"):
            self._w = zstd.ZstdCompressor().stream_writer(self._fh)
        else:
            self._w = self._fh

    def write_block(self, block: SampleBlock) -> int:
        from repro.core import obs  # obs imports this module

        with obs.timed("export.write"):
            return self._write(block)

    def _write(self, block: SampleBlock) -> int:
        lines = getattr(block, "iter_json_lines", None)
        if lines is not None:
            # ColumnBlock export codec: canonical lines assembled straight
            # from the column buffers — no row dicts, byte-identical to the
            # json_dumps path below by the format's round-trip invariant
            for raw in lines():
                self._w.write(raw + b"\n")
                self.n += 1
            return len(block)
        for s in block.samples:
            self._w.write(json_dumps(s) + b"\n")
            self.n += 1
        return len(block)

    def close(self, success: bool = True) -> None:
        if self._fh is None:
            return
        fh, w = self._fh, self._w
        self._fh = None
        flush_err: Optional[BaseException] = None
        try:
            if w is not fh:
                w.close()
            fh.close()
        except Exception as e:  # e.g. zstd flush on a full disk
            flush_err = e
            try:
                fh.close()
            except Exception:
                pass
        if success and flush_err is None:
            os.replace(self._tmp, self.path)  # atomic publish
            return
        try:
            os.remove(self._tmp)
        except OSError:
            pass
        if success and flush_err is not None:
            raise flush_err  # flush failed: nothing was published
        # failure path swallows flush errors — never mask the original one

    def __enter__(self) -> "BlockWriter":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        self.close(success=exc_type is None)


class BlockPrefetcher:
    """Bounded prefetch queue: a background thread decodes blocks from
    ``source`` into a queue of at most ``depth`` blocks, overlapping JSONL
    decode with downstream op compute while capping memory. ``max_depth``
    tracks the deepest the queue ever got (always <= ``depth``)."""

    _DONE = object()

    def __init__(self, source: Iterable[SampleBlock], depth: int = 4):
        import queue
        import threading

        self.depth = max(1, depth)
        self.max_depth = 0
        self._queue_mod = queue
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._err: Optional[BaseException] = None
        self._stopped = False
        self._t = threading.Thread(target=self._fill, args=(iter(source),), daemon=True)
        self._t.start()

    def _put(self, item) -> bool:
        """Stop-aware put: never blocks forever on an abandoned consumer."""
        while not self._stopped:
            try:
                self._q.put(item, timeout=0.1)
                return True
            except self._queue_mod.Full:
                continue
        return False

    def _fill(self, source: Iterator[SampleBlock]) -> None:
        try:
            for blk in source:
                if not self._put(blk):
                    return
                self.max_depth = max(self.max_depth, self._q.qsize())
        except BaseException as e:  # propagate to the consumer
            self._err = e
        finally:
            self._put(self._DONE)

    def close(self) -> None:
        """Release the fill thread (and the blocks it holds) — called
        automatically when the consuming iterator is dropped."""
        self._stopped = True
        while True:  # drain so a blocked put wakes immediately
            try:
                self._q.get_nowait()
            except self._queue_mod.Empty:
                return

    def __iter__(self) -> Iterator[SampleBlock]:
        from repro.core import obs  # obs imports this module

        try:
            while True:
                # the consumer's wait for the next decoded input block
                with obs.timed("stream.wait"):
                    item = self._q.get()
                if item is self._DONE:
                    if self._err is not None:
                        raise self._err
                    return
                yield item
        finally:
            self.close()


def presplit_jsonl(
    src: str, out_dir: str, block_bytes: int = DEFAULT_BLOCK_BYTES, n_workers: int = 1
) -> List[str]:
    """Pre-split a JSONL file into ~block_bytes shards on disk."""
    os.makedirs(out_dir, exist_ok=True)
    total = os.path.getsize(src)
    if n_workers > 1:
        block_bytes = max(1, min(block_bytes, total // n_workers))
    paths: List[str] = []
    buf: List[bytes] = []
    nb = 0

    def flush():
        nonlocal buf, nb
        if not buf:
            return
        p = os.path.join(out_dir, f"part-{len(paths):05d}.jsonl")
        with open(p, "wb") as f:
            f.write(b"".join(buf))
        paths.append(p)
        buf, nb = [], 0

    with _open_read(src) as f:
        for line in f:
            raw = line.encode("utf-8") if isinstance(line, str) else line
            if nb + len(raw) > block_bytes and buf:
                flush()
            buf.append(raw)
            nb += len(raw)
    flush()
    return paths
