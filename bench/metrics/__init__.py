"""One reader per metric: ``<name>.py`` defines ``read(ctx)``, which returns
the metric's value, or None when the run holds nothing to read it from.

``ctx`` holds: ``cfg`` (the configuration file), ``snaps`` (one snapshot per
output block: host time ``t``, wall time ``wall``, input documents ``n_in``
consumed so far, the per-op ``seconds`` and ``ops`` of the program's live
progress entries), ``window_s``, ``attempted``, ``setup_s``, ``spans`` (the
program's trace spans), ``checked`` (what ``bench/check.py`` found),
``trace`` (``bench/trace.py``'s summary, traced runs only) and ``peaks``
(this device's row of ``bench/peaks.json``, traced runs only).
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def op_seconds_per_kdoc(ctx: Dict[str, Any], pick) -> Optional[float]:
    """Seconds the ops chosen by ``pick(name)`` spent in the window, per
    1,000 input documents; None when no op is chosen or no document came."""
    s0, s1 = ctx["snaps"][0], ctx["snaps"][-1]
    idx = [i for i, name in enumerate(s1["ops"]) if pick(name)]
    if not idx or ctx["attempted"] <= 0:
        return None
    secs = sum(s1["seconds"][i] - s0["seconds"][i] for i in idx)
    return secs / (ctx["attempted"] / 1000.0)
