#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one process, one window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine whose JAX sees a TPU; with no
TPU, or fewer chips than the cell asks for, it exits 1 and prints no result.

A cell names a configuration (``bench/configs/<name>.json``: the recipe, its
scorer and cuts) and a traffic mix (``bench/traffic/<name>.json``, read by
``bench/traffic.py``). One run:

1. set-up: turns on the compile cache, writes the corpus from ``--seed``,
   builds the recipe as a ``repro.api.Pipeline`` on the local engine with the
   configuration's ``block_bytes``, and starts the streaming executor
   (``Executor.stream_blocks``); each output block is exported through the
   program's ``BlockWriter``. Set-up ends when the first output block has
   been written: probe, optimizer, model init and compiles lie inside it;
2. the window: output blocks are written until the first one written after
   ``--seconds``; then the stream is stopped through its ``cancel`` hook.
   With ``--trace 1`` the profiler records the window;
3. the check (``bench/check.py``): what the window exported, compared with
   the plain references under ``bench/reference/``.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (input documents behind the window's blocks), ``failed`` (op
errors in the window), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones, each read by ``bench/metrics/<name>.py``),
``device``, ``breakdown`` when traced, and ``checks`` last: each compared
number beside its limit, also printed as the last lines of standard error.
Everything else goes on earlier lines. Work files go to ``.bench_work/``
under the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

WORK = ROOT / ".bench_work"


@functools.cache
def compile_log() -> List[tuple]:
    """The list that JAX's compile events of this process are appended to
    from now on: ``(host time at the end, program, seconds)`` of each XLA
    compile or compile-cache load."""
    import jax

    log: List[tuple] = []

    def on_duration(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            log.append((time.perf_counter(), str(kw.get("fun_name", "?")), secs))
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return log


def load_spec() -> Dict[str, Any]:
    with open(ROOT / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def load_config(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    with open(ROOT / entry["file"], encoding="utf-8") as f:
        return json.load(f)


def build_pipeline(cfg: Dict[str, Any], corpus: str):
    import repro.api as dj

    pipe = dj.read_jsonl(corpus).options(engine="local", block_bytes=int(cfg["block_bytes"]))
    for step in cfg["process"]:
        pipe = pipe.op(step["name"], **{k: v for k, v in step.items() if k != "name"})
    return pipe


def dispatch_sizes(cfg: Dict[str, Any], corpus: str, max_words: int) -> Tuple[List[int], int]:
    """``(counts, s)``: every document count a MinHash signature dispatch of
    the stream can have, and the shingle count of the corpus's widest
    document. A super-batch flushes once ``super_batch`` documents are
    pending, after a whole input block is added, so it holds from
    ``super_batch`` to ``super_batch - 1 +`` the most documents one block
    can hold."""
    step = next((s for s in cfg["process"] if "deduplicator" in s["name"]), None)
    if step is None or not step.get("use_kernel"):
        return [], 0
    import numpy as np

    ends = np.flatnonzero(np.fromfile(corpus, np.uint8) == 10)
    block_docs = int(np.max(np.searchsorted(ends, ends + cfg["block_bytes"]) - np.arange(len(ends))))
    sb = int(step.get("super_batch", 2048))
    s = max(1, min(cfg["minhash"]["max_shingles"], max_words - step["ngram"] + 1))
    return list(range(sb, sb + block_docs)), s


def warm_kernels(cfg: Dict[str, Any], counts: List[int], s: int) -> None:
    """Compile, or load from the compile cache, what a signature dispatch of
    ``d`` documents runs on the device, for each ``d`` in ``counts``: one
    call of the program's packed dispatch (the one its signature batcher
    makes) with one document of ``s`` shingles and ``d - 1`` of one."""
    if not counts:
        return
    import numpy as np

    from repro.kernels.minhash.ops import minhash_signatures_packed

    p = next(st for st in cfg["process"] if "deduplicator" in st["name"])["num_permutations"]
    a, b = np.ones(p, np.uint64), np.zeros(p, np.uint64)
    for d in counts:
        offsets = np.concatenate([[0], np.arange(s, s + d, dtype=np.int64)])
        np.asarray(minhash_signatures_packed(np.zeros(s + d - 1, np.uint64), offsets, a, b))


def snapshot(entries: List[dict], n_out: int) -> Dict[str, Any]:
    return {"t": time.perf_counter(), "wall": time.time(), "n_in": entries[0]["in"] if entries else 0,
            "n_out": n_out, "errors": sum(e["errors"] for e in entries),
            "seconds": [e["seconds"] for e in entries], "ops": [e["op"] for e in entries]}


def stream_window(cfg: Dict[str, Any], corpus: str, export: str, seconds: float,
                  trace_dir: Optional[str], name: str) -> Dict[str, Any]:
    """Set-up, window and stop. Returns the block snapshots, the spans the
    program recorded, and where the traced window started and ended."""
    import jax

    from repro.core import obs
    from repro.core.dataset import ExecutionCancelled
    from repro.core.executor import Executor
    from repro.core.storage import BlockWriter

    ex = Executor(build_pipeline(cfg, corpus).to_recipe(name))
    entries: List[dict] = []
    stop = {"now": False}
    snaps: List[Dict[str, Any]] = []
    trace_id = obs.new_id()
    exhausted = True
    traced = None
    n_out = 0
    writer = BlockWriter(export)
    with obs.span(trace_id, f"bench:{name}", kind="run"):
        gen = ex.stream_blocks(monitor=entries, cancel=lambda: stop["now"])
        try:
            while True:
                with jax.profiler.TraceAnnotation("bench.pipeline"):
                    blk = next(gen, None)
                if blk is None:
                    break
                with jax.profiler.TraceAnnotation("bench.export"):
                    writer.write_block(blk)
                n_out += len(blk)
                snaps.append(snapshot(entries, n_out))
                if len(snaps) == 1:
                    if trace_dir:
                        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
                        traced = [time.perf_counter(), None]
                    snaps[0]["t"], snaps[0]["wall"] = time.perf_counter(), time.time()
                elif snaps[-1]["t"] - snaps[0]["t"] >= seconds:
                    exhausted = False
                    break
        finally:
            if traced is not None:
                traced[1] = time.perf_counter()
                jax.profiler.stop_trace()
            stop["now"] = True
            try:
                next(gen, None)
            except ExecutionCancelled:
                pass
            gen.close()
            writer.close(success=True)
    spans = obs.drain(trace_id)
    return {"snaps": snaps, "exhausted": exhausted, "spans": spans, "traced": traced,
            "plan": [e["op"] for e in entries]}


def profile_options():
    """Device and host events, without Python function events: the Python
    tracer slows the host code it records and would distort the per-layer
    times the traced run reports."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def device_info(jax) -> Dict[str, Any]:
    devs = jax.devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else 0}


def peaks_for(kind: str) -> Dict[str, float]:
    """This device's row of ``bench/peaks.json``; a kind the table lacks is
    an error."""
    with open(ROOT / "bench" / "peaks.json", encoding="utf-8") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def read_metrics(names: List[Dict[str, Any]], ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each metric by its own reader ``bench/metrics/<name>.py``; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in names:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(spec: Dict[str, Any], cell: Dict[str, Any], seed: int, seconds: float,
             trace: bool, t_start: float = T_START,
             detail: Optional[Dict[str, Any]] = None,
             work_root: Path = WORK,
             program_cfg: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object, and fills ``detail``
    (when given) with the check's findings and the run's files, which go
    under ``work_root``. ``program_cfg`` (a control) is the configuration
    the stream runs, where it differs from the cell's, which the check holds
    it to. Needs JAX importable; does not look at what device it runs on."""
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # every program goes to the persistent cache, the small ones a signature
    # dispatch runs for each new document count too, so that a warm run
    # loads them all in set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = compile_log()
    from bench import check, traffic
    from bench import trace as tr

    cfg = load_config(spec, cell["config"])
    run_cfg = program_cfg or cfg
    mix = traffic.load_mix(cell["traffic"])
    work = work_root / cell["name"]
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    corpus, export = str(work / "corpus.jsonl"), str(work / "export.jsonl")
    t_gen = time.perf_counter()
    info = traffic.generate(mix, seed, corpus)
    print(f"corpus: {json.dumps(info)} in {time.perf_counter() - t_gen:.3f} s", flush=True)
    t_warm = time.perf_counter()
    counts, s = dispatch_sizes(run_cfg, corpus, info["max_words"])
    warm_kernels(run_cfg, counts, s)
    print(f"warm-up: {len(counts)} signature dispatches of {counts[0] if counts else 0}-"
          f"{counts[-1] if counts else 0} documents, widest {s} shingles, in "
          f"{time.perf_counter() - t_warm:.3f} s", flush=True)
    trace_dir = str(work / "trace") if trace else None
    run = stream_window(run_cfg, corpus, export, seconds, trace_dir, cell["name"])
    snaps = run["snaps"]
    if not snaps:
        raise RuntimeError("the stream wrote no output block")
    s0, s1 = snaps[0], snaps[-1]
    setup_s = s0["t"] - t_start
    window_s = s1["t"] - s0["t"]
    attempted = s1["n_in"] - s0["n_in"]
    device = device_info(jax)
    print(f"window: {json.dumps({'blocks': len(snaps) - 1, 'seconds': window_s, 'docs_in': attempted, 'rows_out': s1['n_out'] - s0['n_out'], 'corpus_docs': info['docs'], 'corpus_exhausted': run['exhausted'], 'plan': run['plan']})}",
          flush=True)
    if run["exhausted"]:
        print("window: the corpus ran out before the window's end; "
              "metrics are over the shortened window", flush=True)
    print(f"memory: peak_bytes_in_use={device['memory_peak_bytes']}", flush=True)
    print(f"window per-op seconds: {json.dumps(dict(zip(s1['ops'], (b - a for a, b in zip(s0['seconds'], s1['seconds'])))))}",
          flush=True)
    inside = [c for c in compiles if s0["t"] < c[0] <= s1["t"]]
    if detail is not None:
        detail.update(window_compiles=inside, exhausted=run["exhausted"])
    print(f"window compiles: {json.dumps({'count': len(inside), 'seconds': sum(c[2] for c in inside), 'programs': sorted({c[1] for c in inside})})}",
          flush=True)
    t_check = time.perf_counter()
    gc.collect()  # the program's state (the scorer's weights) goes before the references

    checks = check.run(cfg, corpus, export, s1["n_in"], seed, window_rows=(s0["n_out"], s1["n_out"]))
    print(f"check: {time.perf_counter() - t_check:.3f} s", flush=True)
    rows = checks.get("scorer_rows")
    if rows:
        worst = max(range(len(rows["gaps"])), key=rows["gaps"].__getitem__)
        print(f"scorer rows: {len(rows['gaps'])} checked, widest gap {rows['gaps'][worst]} nats "
              f"on a row of {rows['tokens'][worst]} scored tokens", flush=True)
    if detail is not None:
        detail.update(cfg=cfg, checks=checks, corpus=corpus, export=export, n_in=s1["n_in"],
                      window_rows=(s0["n_out"], s1["n_out"]))
    ctx = {"cfg": cfg, "cell": cell, "snaps": snaps, "spans": run["spans"],
           "window_s": window_s, "attempted": attempted, "setup_s": setup_s,
           "device": device, "checked": checks, "trace": None}
    result: Dict[str, Any] = {"correct": all(c["ok"] for c in checks["numbers"].values()),
                              "attempted": attempted, "failed": s1["errors"] - s0["errors"]}
    if trace:
        t0, t1 = run["traced"]
        devices, host = tr.load(trace_dir)
        print(f"trace: {json.dumps(tr.extent(devices, host))}", flush=True)
        summary = tr.reduce(devices, host, t1 - t0)
        ctx["trace"] = summary
        ctx["peaks"] = peaks_for(device["kind"])
        names = [m for m in spec["per_layer"] if cell["name"] in m.get("workloads", [cell["name"]])]
        result["metrics"] = read_metrics(names, ctx)
        device = dict(device, busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["device"] = device
        result["breakdown"] = {"device_ops": summary["ops"], "idle_gaps": summary["gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        names = [m for m in spec["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
        result["metrics"] = read_metrics(names, ctx)
        result["device"] = device
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks["numbers"].items()}
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec()
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"run: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"run: needs {cell['chips']} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 1
    result = run_cell(spec, cell, args.seed, args.seconds, bool(args.trace))
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
