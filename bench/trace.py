"""Reduction of a JAX profiler trace to device busy time, module time and
idle gaps.

An event is ``(name, start_ns, dur_ns)``. On each device plane
(``/device:TPU:<n>``) the line ``XLA Ops`` carries one event per operation
the device ran, and ``XLA Modules`` one per compiled program it ran
(``jit_<function>(<id>)``). Busy time is the union of a plane's operation
intervals; module time is the summed duration of a module's events. Host
planes carry the host's own events (``TraceAnnotation`` spans and JAX's
dispatch events). Every plane counts nanoseconds from the session's start.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events: Sequence[Event]) -> float:
    return sum(e - s for s, e in merge((s, s + d) for _, s, d in events))


def gaps(events: Sequence[Event], end_ns: Optional[float] = None) -> List[Tuple[float, float]]:
    """``(start, length)`` of each idle stretch of the window ``[0, end_ns]``
    (trace time starts with the session): between busy intervals, and
    before the first and after the last."""
    m = merge((s, s + d) for _, s, d in events)
    if end_ns is not None:
        m = [(-1.0, 0.0)] + m + [(end_ns, end_ns + 1.0)]
    return [(a[1], b[0] - a[1]) for a, b in zip(m, m[1:]) if b[0] > a[1]]


def module_name(event_name: str) -> str:
    return _MODULE_SUFFIX.sub("", event_name)


def module_seconds(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, d in events:
        key = module_name(name)
        out[key] = out.get(key, 0.0) + d * 1e-9
    return out


def op_kind(event_name: str) -> str:
    """An XLA Ops event is named by its HLO text, ``%name.N = shape
    opcode(operands), ...``; its kind is the opcode (``fusion``, ``while``,
    ``custom-call``, ...), which stays put when instruction numbers move.
    A bare ``name.N`` gives ``name``."""
    if " = " not in event_name:
        return event_name.split("(", 1)[0].lstrip("%").rsplit(".", 1)[0]
    rest = event_name.split(" = ", 1)[1]
    depth = 0
    for i, c in enumerate(rest):  # the shape ends at the first top-level space
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0]
    return rest.split("(", 1)[0]


def top_ops(ops: Sequence[Event], modules: Sequence[Event], n: int = 10) -> List[List]:
    """Device time by ``<module>/<opkind>``, the ``n`` largest; an op belongs
    to the module event that covers its start. Nested ops (a loop and the
    fusions in its body) each count their own time."""
    spans = sorted((s, s + d, module_name(name)) for name, s, d in modules)
    starts = [sp[0] for sp in spans]
    tot: Dict[str, float] = {}
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = spans[i][2] if i >= 0 and s <= spans[i][1] else "?"
        key = f"{mod}/{op_kind(name)}"
        tot[key] = tot.get(key, 0.0) + d * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def name_gaps(gap_list: Sequence[Tuple[float, float]], host: Sequence[Event],
              n: int = 10) -> List[List]:
    """The ``n`` longest gaps, each named by the shortest host event that
    covers its middle (``host idle`` where none does)."""
    out = []
    for start, length in sorted(gap_list, key=lambda g: -g[1])[:n]:
        mid = start + length / 2
        cover = [(d, name) for name, s, d in host if s <= mid <= s + d]
        out.append([min(cover)[1] if cover else "host idle", length * 1e-9])
    return out


def load(trace_dir: str):
    """``(device_planes, host_events)`` of the newest trace under
    ``trace_dir``: ``device_planes`` maps a plane's name to its lines, each
    a list of events."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        lines = {line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events] for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and OPS_LINE in lines:
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for evs in lines.values():
                host.extend(evs)
    return devices, host


def extent(devices: Dict[str, Dict[str, List[Event]]], host: Sequence[Event]) -> Dict:
    """Event counts and the first start and last end (ns) of the device
    operations, of all host events and of the benchmark's own ``bench.*``
    host spans: a check that the planes share one clock."""
    def span(evs):
        return [len(evs), min((s for _, s, _ in evs), default=None),
                max((s + d for _, s, d in evs), default=None)]
    ops = [e for lines in devices.values() for e in lines[OPS_LINE]]
    return {"device_ops": span(ops), "host": span(host),
            "bench": span([e for e in host if e[0].startswith("bench.")])}


def reduce(devices: Dict[str, Dict[str, List[Event]]], host: Sequence[Event],
           window_s: float) -> Dict:
    """Per-trace summary: ``busy_s`` averaged over the device planes,
    ``module_s`` and ``ops`` summed over them, and the longest idle gaps of
    the busiest plane named by host activity."""
    if not devices:
        return {"busy_s": 0.0, "window_s": window_s, "module_s": {}, "ops": [], "gaps": []}
    busy = {p: busy_ns(lines[OPS_LINE]) * 1e-9 for p, lines in devices.items()}
    modules: Dict[str, float] = {}
    all_ops: List[Event] = []
    all_mods: List[Event] = []
    for lines in devices.values():
        for k, v in module_seconds(lines.get(MODULES_LINE, [])).items():
            modules[k] = modules.get(k, 0.0) + v
        all_ops.extend(lines[OPS_LINE])
        all_mods.extend(lines.get(MODULES_LINE, []))
    busiest = max(busy, key=busy.get)
    return {
        "busy_s": sum(busy.values()) / len(busy),
        "window_s": window_s,
        "module_s": modules,
        "ops": top_ops(all_ops, all_mods),
        "gaps": name_gaps(gaps(devices[busiest][OPS_LINE], window_s * 1e9), host),
    }


def module_time(summary: Dict, prefix: str) -> Optional[float]:
    """Seconds of the modules whose name starts with ``prefix``; None when
    the trace holds none."""
    hits = [v for k, v in summary["module_s"].items() if k.startswith(prefix)]
    return sum(hits) if hits else None
