#!/usr/bin/env python3
"""Readings that the check's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds <a,b,...> --seconds <s> [--witness]

For each seed, one run of the cell as ``bench/run.py`` makes it (a short
window is enough) gives the program's reading of each compared number. Then
the control is put in the program's place and judged by the same check, under
the cell's own limits (``bench/check.py``), which has to find it not correct:

- a cell with a scorer (``scorer_gap_nats``): the exported ``lm_ppl`` of
  the checked rows replaced by those of the reference the scorer block
  names, every matmul operand rounded to its ``control_dtype``
  (``float8_e4m3fn``, one scale per tensor: one step below the bfloat16 the
  configuration states);
- a cell without one: the stream run with half the configured LSH bands,
  twice the rows each, the cheaper index a later change could be tempted by,
  checked against the configured banding (``dedup_rows_wrong``).

``--witness`` (scorer cells) runs the seed once more with the scorer's batch
built with next-token targets at every scored position
(``next_token_rows``): the program's own model and step, its labeling
repaired.

Prints one JSON line per seed, then the largest program reading and the
smallest control reading of each number. The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def next_token_rows(tok, texts, seq: int):
    """The scorer's ``(tokens, labels, mask)`` batch with the target of each
    scored position the token that follows it: the next word, the end of
    text after the last, or the first word past the cut."""
    toks = np.zeros((len(texts), seq), np.int32)
    labels = np.zeros((len(texts), seq), np.int32)
    mask = np.zeros((len(texts), seq), np.float32)
    for i, t in enumerate(texts):
        ids = tok.encode(t)[:seq + 1]
        n = len(ids) - 1
        toks[i, :n] = ids[:-1]
        labels[i, :n] = ids[1:]
        mask[i, :n] = 1.0
    return toks, labels, mask


@contextlib.contextmanager
def repaired_labels():
    """The scorer op builds its batches with ``next_token_rows`` inside."""
    from repro.ops import model_ops

    saved = getattr(model_ops, "encode_rows", None)
    model_ops.encode_rows = next_token_rows
    try:
        yield
    finally:
        model_ops.encode_rows = saved


def half_bands(cfg: Dict[str, Any]) -> Dict[str, Any]:
    ctl = json.loads(json.dumps(cfg))
    step = next(s for s in ctl["process"] if "deduplicator" in s["name"])
    step["num_bands"] //= 2
    return ctl


def scorer_control(detail: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The check's result with the ``lm_ppl`` of the scorer's reference in
    its ``control_dtype`` in place of the program's, on the rows the check
    compares."""
    from bench import check
    from bench.reference import scorer_reference

    cfg = detail["cfg"]
    sc = cfg["scorer"]
    got = [json.loads(x) for x in check.read_lines(detail["export"])]
    pick = check.pick_rows(cfg, got, seed)
    low = scorer_reference(sc).row_losses(sc, [got[i]["text"] for i in pick], sc["seq_len"],
                                          quantize=sc["control_dtype"])
    for i, loss in zip(pick, low):
        got[i]["stats"]["lm_ppl"] = math.exp(float(loss))
    path = detail["export"] + ".control"
    with open(path, "wb") as f:
        f.writelines(check.canonical(r) + b"\n" for r in got)
    w = detail["window_rows"]
    return check.run(cfg, detail["corpus"], path, detail["n_in"], seed, window_rows=w)


def numbers(checks: Dict[str, Any]) -> Dict[str, Any]:
    return {k: c["value"] for k, c in checks["numbers"].items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--witness", action="store_true")
    args = ap.parse_args()
    from bench import run

    spec = run.load_spec()
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload), None)
    if cell is None:  # a configuration kept for a later cell
        config, traffic = args.workload.split(".", 1)
        cell = {"name": args.workload, "config": config, "traffic": traffic, "chips": 1}
        spec["configs"] = [{"name": config, "file": f"bench/configs/{config}.json"}]
    prog, ctl = {}, {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        detail: Dict[str, Any] = {}
        t0 = time.perf_counter()
        res = run.run_cell(spec, cell, seed, args.seconds, False, t_start=t0, detail=detail)
        line = {"seed": seed, "correct": res["correct"], "program": numbers(detail["checks"]),
                "docs_per_s": res["metrics"]["docs_per_s"]["value"]}
        rows = detail["checks"].get("scorer_rows")
        if rows:
            line["widest"] = sorted(zip(rows["gaps"], rows["tokens"]), reverse=True)[:3]
            control = scorer_control(detail, seed)
            if args.witness:
                wd: Dict[str, Any] = {}
                with repaired_labels():
                    w = run.run_cell(spec, cell, seed, args.seconds, False, detail=wd)
                wrows = wd["checks"]["scorer_rows"]
                line["witness"] = {"correct": w["correct"], "program": numbers(wd["checks"]),
                                   "widest": sorted(zip(wrows["gaps"], wrows["tokens"]),
                                                    reverse=True)[:3]}
        else:
            cd: Dict[str, Any] = {}
            run.run_cell(spec, cell, seed, args.seconds, False, detail=cd,
                         program_cfg=half_bands(detail["cfg"]))
            control = cd["checks"]
        line["control"] = numbers(control)
        line["control_correct"] = all(c["ok"] for c in control["numbers"].values())
        line["seconds"] = time.perf_counter() - t0
        for k, v in line["program"].items():
            prog[k] = max(prog.get(k, v), v)
        for k, v in line["control"].items():
            ctl[k] = min(ctl.get(k, v), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "program_max": prog, "control_min": ctl}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
