"""Tiny cells for CPU tests: the real configurations and mixes with the
scorer at its reference's ``TINY`` size, short sequences, small blocks and
super-batches, and a corpus of a few hundred documents."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

from bench import run
from bench.reference import scorer_reference

# The scorer's limit at this size, set from CPU readings on 64 web rows of
# the program's model with next-token targets (bfloat16, 0.0007 nats) and of
# the float8 control (0.010 nats).
TINY_LIMIT_NATS = 0.005
# a per-layer reader of a scorer cell that BENCHMARK.json does not list
SCORER_METRICS = [{"name": "scorer_s_per_kdoc", "unit": "s/kdoc"}]


def tiny_config(name: str) -> Dict[str, Any]:
    cfg = json.loads((run.ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg["block_bytes"] = 32768
    for step in cfg["process"]:
        if "deduplicator" in step["name"]:
            step["super_batch"] = 128
        if step["name"] == "lm_perplexity_filter":
            step.update(reduced=True, seq_len=16)
    if "scorer" in cfg:
        cfg["scorer"].update(scorer_reference(cfg["scorer"]).TINY)
        cfg["limits"]["scorer_gap_nats"] = TINY_LIMIT_NATS
    return cfg


def tiny_cell(tmp: Path, config: str, traffic: str, n_docs: int):
    """``(spec, cell)`` for a tiny run of ``config`` under ``traffic``."""
    spec = run.load_spec()
    (tmp / f"{config}.json").write_text(json.dumps(tiny_config(config)))
    spec["configs"] = [{"name": config, "file": str(tmp / f"{config}.json")}]
    mix = json.loads((run.ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())
    mix["n_docs"] = n_docs
    (tmp / f"mix_{traffic}.json").write_text(json.dumps(mix))
    cell = {"name": f"{config}.{traffic}", "config": config,
            "traffic": str(tmp / f"mix_{traffic}.json"), "chips": 1}
    # every reader is tried; those that find nothing stay silent
    names = {m["name"] for m in spec["per_layer"]}
    spec["per_layer"] = [dict(m, workloads=[cell["name"]]) for m in spec["per_layer"]
                         + [m for m in SCORER_METRICS if m["name"] not in names]]
    return spec, cell


def run_tiny(tmp: Path, config: str, traffic: str = "web", n_docs: int = 700,
             seed: int = 3_000_000_019, seconds: float = 1.0, trace: bool = False,
             detail=None, program_cfg=None):
    spec, cell = tiny_cell(tmp, config, traffic, n_docs)
    if program_cfg is not None:
        program_cfg = program_cfg(tiny_config(config))
    return run.run_cell(spec, cell, seed, seconds, trace, detail=detail, work_root=tmp / "work",
                        program_cfg=program_cfg)
