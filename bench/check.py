"""The comparison that decides ``correct``: what the timed run exported,
against the plain references of ``bench/reference/``.

The run consumed the first ``n_in`` corpus rows. The references replay
them: the host steps (``reference.text``), then keep-first MinHash dedup
(``reference.minhash``) over the rows that pass, in arrival order. That
gives the rows the run should have exported, with their stats. Numbers
compared, each with its limit in the configuration file:

- ``filter_rows_wrong``: exported rows the reference filters drop, plus
  exported rows whose text or filter stats differ from the reference's;
- ``dedup_rows_wrong``: ids in only one of the exported and the expected
  rows, plus rows whose ``dup_component`` differs, plus 1 when the order
  differs;
- ``export_lines_wrong``: exported lines that are not the compact UTF-8 JSON
  encoding of their own content, or whose fields are not the expected ones;
- ``scorer_gap_nats`` (with a scorer): the widest gap between a row's
  exported ``ln(lm_ppl)`` and the mean loss over the same tokens of the
  reference the scorer block names (``reference.scorer_reference``), on
  ``rows_checked`` exported rows: the one with the most scored tokens and
  the rest drawn with the run's seed.
"""
from __future__ import annotations

import concurrent.futures
import json
import math
import multiprocessing
import os
from typing import Any, Dict, List, Tuple

import numpy as np

from bench.reference import minhash, scorer_reference, text


def read_lines(path: str) -> List[bytes]:
    with open(path, "rb") as f:
        return [line.rstrip(b"\n") for line in f]


def canonical(obj: Any) -> bytes:
    return json.dumps(obj, separators=(",", ":"), ensure_ascii=False).encode("utf-8")


# below this many rows the references run in the calling process
POOL_MIN_ROWS = 4096
CHUNK_ROWS = 1024


def _sketch_rows(args) -> List[Any]:
    """The per-row work of the references, which depends on no other row,
    for the corpus bytes ``[start, end)``: each row's id, and, for a row the
    host steps keep, its text, stats, shingle hashes and band keys."""
    cfg, corpus, start, end = args
    steps = text.host_steps(cfg["process"])
    kf = _keep_first(cfg)
    with open(corpus, "rb") as f:
        f.seek(start)
        lines = f.read(end - start).splitlines()
    out = []
    for raw in lines:
        row = json.loads(raw)
        t, stats = text.apply(row["text"], steps)
        out.append((row["meta"], None) if stats is None else (row["meta"], (t, stats, *kf.sketch(t))))
    return out


def _keep_first(cfg: Dict[str, Any]) -> minhash.KeepFirst:
    dstep = next(s for s in cfg["process"] if "deduplicator" in s["name"])
    return minhash.KeepFirst(dstep, cfg["minhash"]["max_shingles"], cfg["minhash"]["seed"])


def sketched_rows(cfg: Dict[str, Any], corpus: str, n_in: int, workers: int = -1):
    """``_sketch_rows`` over the first ``n_in`` corpus rows, in order: in
    ``workers`` processes (``-1``: one per CPU, up to 8, once there are
    ``POOL_MIN_ROWS`` rows), or in this one (``0``). The processes are
    spawned, import no JAX, and have ended when this returns."""
    ends = np.flatnonzero(np.fromfile(corpus, np.uint8) == 10)[:n_in] + 1
    starts = np.concatenate([[0], ends[:-1]])
    cuts = list(range(0, len(ends), CHUNK_ROWS))
    jobs = [(cfg, corpus, int(starts[c]), int(ends[min(c + CHUNK_ROWS, len(ends)) - 1]))
            for c in cuts]
    if workers < 0:
        workers = min(8, os.cpu_count() or 1) if len(ends) >= POOL_MIN_ROWS else 0
    if workers == 0:
        return [r for job in jobs for r in _sketch_rows(job)]
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as ex:
        return [r for part in ex.map(_sketch_rows, jobs) for r in part]


def expected_rows(cfg: Dict[str, Any], corpus: str, n_in: int, workers: int = -1) -> Dict[str, Any]:
    """The rows the run should have exported from the first ``n_in`` corpus
    rows, the ids of the rows the host steps keep, and the shingle count of
    each row that reached dedup, in order."""
    kf = _keep_first(cfg)
    rows: Dict[int, Dict[str, Any]] = {}
    order: List[int] = []
    passed = set()
    for meta, kept in sketched_rows(cfg, corpus, n_in, workers):
        if kept is None:
            continue
        t, stats, h, keys = kept
        passed.add(meta["id"])
        gid = len(kf.parent)
        if kf.ingest(h, keys):
            rows[meta["id"]] = {"text": t, "meta": meta, "stats": dict(stats, dup_component=gid)}
            order.append(meta["id"])
    return {"rows": rows, "order": order, "passed": passed, "dedup_shingles": kf.n_shingles}


def pick_rows(cfg: Dict[str, Any], got: List[Dict[str, Any]], seed: int) -> List[int]:
    """Indices of the ``rows_checked`` exported rows the scorer is checked
    on: the one with the most scored tokens, and the rest drawn with the
    run's seed."""
    sc = cfg["scorer"]
    n = min(sc["rows_checked"], len(got))
    real_tokens = scorer_reference(sc).real_tokens
    toks = [real_tokens(r["text"], sc["seq_len"]) for r in got]
    longest = int(np.argmax(toks))
    rest = [i for i in range(len(got)) if i != longest]
    return [longest] + sorted(np.random.default_rng(seed).choice(rest, n - 1, replace=False).tolist()
                              if n > 1 else [])


def scorer_gap(cfg: Dict[str, Any], got: List[Dict[str, Any]], seed: int
               ) -> Tuple[float, Dict[str, Any]]:
    """Widest |ln(lm_ppl) - reference loss| over the checked rows, and the
    rows with both readings."""
    sc = cfg["scorer"]
    model = scorer_reference(sc)
    pick = pick_rows(cfg, got, seed)
    texts = [got[i]["text"] for i in pick]
    ref = model.row_losses(sc, texts, sc["seq_len"])
    gaps = []
    for i, r in zip(pick, ref):
        ppl = got[i].get("stats", {}).get("lm_ppl")
        ok = isinstance(ppl, float) and math.isfinite(ppl) and ppl > 0
        gaps.append(abs(math.log(ppl) - r) if ok else math.inf)
    return max(gaps), {"texts": texts, "ref": ref.tolist(), "gaps": gaps,
                       "tokens": [model.real_tokens(t, sc["seq_len"]) for t in texts]}


def run(cfg: Dict[str, Any], corpus: str, export: str, n_in: int, seed: int,
        window_rows: Tuple[int, int]) -> Dict[str, Any]:
    exp = expected_rows(cfg, corpus, n_in)
    lines = read_lines(export)
    got = [json.loads(x) for x in lines]
    has_scorer = "scorer" in cfg
    filt_bad = dedup_bad = lines_bad = 0
    for raw, row in zip(lines, got):
        rid = row.get("meta", {}).get("id")
        want = exp["rows"].get(rid)
        stats = dict(row.get("stats", {}))
        ppl = stats.pop("lm_ppl", None)
        fields_ok = set(row) == {"text", "meta", "stats"} and (ppl is not None) == has_scorer
        if raw != canonical(row) or not fields_ok:
            lines_bad += 1
        if want is None:
            continue
        comp = stats.pop("dup_component", None)
        if row.get("text") != want["text"] or stats != {k: v for k, v in want["stats"].items()
                                                       if k != "dup_component"}:
            filt_bad += 1
        if comp != want["stats"]["dup_component"]:
            dedup_bad += 1
    got_ids = [r.get("meta", {}).get("id") for r in got]
    # an exported row the reference filters drop is a filter fault; one they
    # keep but dedup drops is a dedup fault
    extra = set(got_ids) - set(exp["order"])
    passed = extra & exp["passed"]
    filt_bad += len(extra - passed)
    dedup_bad += len(passed) + len(set(exp["order"]) - set(got_ids))
    dedup_bad += int(got_ids != exp["order"])
    limits = cfg["limits"]
    out: Dict[str, Any] = {"numbers": {}, "dedup_shingles": exp["dedup_shingles"]}
    numbers = {
        "filter_rows_wrong": filt_bad,
        "dedup_rows_wrong": dedup_bad,
        "export_lines_wrong": lines_bad,
    }
    if has_scorer:
        gap, out["scorer_rows"] = scorer_gap(cfg, got, seed) if got else (math.inf, {})
        numbers["scorer_gap_nats"] = gap
        w0, w1 = window_rows
        sc = cfg["scorer"]
        real_tokens = scorer_reference(sc).real_tokens
        out["window_tokens"] = sum(real_tokens(r["text"], sc["seq_len"]) for r in got[w0:w1])
    for k, v in numbers.items():
        out["numbers"][k] = {"value": v, "limit": limits[k], "ok": v <= limits[k]}
    return out

