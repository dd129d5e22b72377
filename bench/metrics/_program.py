"""The program's own annotations in the traced window.

While the profiler records, the program's ``obs`` opens a profiler
annotation for each of its timed scopes (``dedup.shingle``, ``dedup.sign``,
``dedup.index`` with ``dedup.verify`` inside it, ``stream.wait``,
``export.write``, ``scorer.encode``, ``scorer.step``), whose metadata carries
the scope's counts (``scorer.tokens_scored``, ``scorer.tokens_padded``, ...).
The harness keeps the window's trace under ``<work>/<cell>/trace`` until the
readers have run; this module reads its host events once per trace. A
program without these annotations leaves the readers nothing to read.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import Any, Dict, Optional

from bench import run


@functools.lru_cache(maxsize=1)
def _totals(path: str, mtime: float) -> Dict[str, Dict[str, float]]:
    """Per event name on the host planes: summed ``seconds``, ``count``,
    and the sum of each numeric stat."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, float]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                t = out.setdefault(e.name, {"seconds": 0.0, "count": 0.0})
                t["seconds"] += e.duration_ns * 1e-9
                t["count"] += 1
                for k, v in e.stats:
                    if isinstance(v, (int, float)) and not isinstance(v, bool):
                        t[k] = t.get(k, 0.0) + v
    return out


def annotations(ctx: Dict[str, Any]) -> Optional[Dict[str, Dict[str, float]]]:
    """Totals by annotation name in this run's trace; None in an untraced
    run or where the trace is gone."""
    if not ctx.get("trace"):
        return None
    files = glob.glob(os.path.join(str(run.WORK / ctx["cell"]["name"] / "trace"),
                                   "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    newest = max(files, key=os.path.getmtime)
    return _totals(newest, os.path.getmtime(newest))


def seconds_per_kdoc(ctx: Dict[str, Any], name: str) -> Optional[float]:
    """Seconds under the annotation ``name`` in the traced window (nested
    annotations included), per 1,000 input documents; None where the trace
    holds no such annotation."""
    ann = annotations(ctx)
    if not ann or name not in ann or ctx["attempted"] <= 0:
        return None
    return ann[name]["seconds"] / (ctx["attempted"] / 1000.0)
