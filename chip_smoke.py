#!/usr/bin/env python3
"""Bring-up check: the recipe path on one TPU chip.

    python3 chip_smoke.py

Runs in one process, from the root of a checkout, and needs one TPU chip;
it exits nonzero, printing no result, when JAX finds none. Phases, in
order, each of which raises on failure:

1. device  — the first JAX device is a TPU.
2. kernel  — the MinHash kernel (compiled, not interpreted) at 2048 docs x
             512 shingles x 128 permutations equals a numpy computation of
             the same uint32 hash family, bit for bit.
3. pipeline — a 4096-doc synthetic corpus through
             whitespace_normalization_mapper -> text_length_filter ->
             alnum_ratio_filter -> lm_perplexity_filter (mamba2-1.3b at full
             width, random weights from a seed, 256 tokens) ->
             document_minhash_deduplicator (kernel, streaming "exact") ->
             export, through ``repro.api.Pipeline`` on the local engine.
             No op errors, rows out, finite ``lm_ppl`` on every row, and the
             MinHash signatures came from the kernel.
4. scorer  — for 8 exported rows, the model's ``loss_fn`` on each row alone
             at the highest matmul precision agrees with the op's batched
             score within SCORER_TOL_NATS.
5. dedup   — the scorer-free prefix plus dedup on the kernel path exports
             the same bytes with streaming "exact" and "off", and keeps the
             same rows as phase 3.

The last line of standard output is one JSON object naming the device.
Outputs go to ``chiprun_out/chip_smoke/`` under the checkout.
"""
from __future__ import annotations

import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"

N_DOCS = 4096
ARCH = "mamba2-1.3b"
SEQ_LEN = 256
KERNEL_SHAPE = (2048, 512, 128)  # streaming super-batch x max_shingles x permutations
N_SCORER_ROWS = 8
# The op scores a batch of 64 at the default matmul precision; the check
# scores one row at "highest". Activations are bf16 either way, so what
# differs is the f32 SSD einsums, which the default precision feeds to the
# MXU as bf16 (unit roundoff 2**-9). Over 48 residual layers that perturbs
# the final hidden state by O(1e-2) relative; with random-init logits of
# O(1) that moves a row's mean loss (~10.8 nats) by O(1e-2) nats. 0.05 nats
# (lm_ppl within 5%) leaves a margin over that and stays below the spread
# of mean losses between rows.
SCORER_TOL_NATS = 0.05

PREFIX = (
    ("map", "whitespace_normalization_mapper", {}),
    ("filter", "text_length_filter", {"min_val": 40}),
    ("filter", "alnum_ratio_filter", {"min_val": 0.75}),
)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def device_memory() -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use") if k in stats}


def check_kernel(d: int, s: int, p: int, seed: int = 0) -> None:
    """MinHash kernel vs numpy on a seeded input: exact equality."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.minhash.minhash import minhash_pallas
    from repro.kernels.minhash.ops import minhash_signatures

    rng = np.random.default_rng(seed)
    h = rng.integers(0, 2**64, (d, s), dtype=np.uint64)
    mask = rng.random((d, s)) > 0.3
    mask[:, 0] = True
    a = rng.integers(1, 2**61, p, dtype=np.uint64)
    b = rng.integers(0, 2**61, p, dtype=np.uint64)

    # ref.py semantics in numpy: fold hashes to 32 bits, odd multipliers,
    # (a*h + b) mod 2**32, min over valid shingles
    h32 = (h & 0xFFFFFFFF).astype(np.uint32) ^ (h >> np.uint64(32)).astype(np.uint32)
    a32 = a.astype(np.uint32) | np.uint32(1)
    b32 = b.astype(np.uint32)
    ref = np.empty((d, p), np.uint32)
    for i in range(0, d, 64):
        vals = a32[None, :, None] * h32[i:i + 64, None, :] + b32[None, :, None]
        vals = np.where(mask[i:i + 64, None, :], vals, np.uint32(0xFFFFFFFF))
        ref[i:i + 64] = vals.min(axis=2)

    lowered = minhash_pallas.lower(jnp.asarray(h32), jnp.asarray(mask),
                                   jnp.asarray(a32), jnp.asarray(b32))
    require("tpu_custom_call" in lowered.as_text(),
            "the MinHash kernel was not lowered as a compiled TPU kernel")
    t0 = time.perf_counter()
    out = np.asarray(minhash_signatures(h, mask, a, b))
    print(f"kernel: D={d} S={s} P={p} first call (compile + run) "
          f"{time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    np.asarray(minhash_signatures(h, mask, a, b))
    print(f"kernel: second call {time.perf_counter() - t0:.3f}s")
    require(out.dtype == np.uint32 and out.shape == (d, p), f"kernel output {out.dtype} {out.shape}")
    require(np.array_equal(out, ref),
            f"kernel differs from numpy in {int((out != ref).sum())} of {out.size} values")


def prefix_pipeline(corpus: str):
    import repro.api as dj

    pipe = dj.read_jsonl(corpus).options(engine="local")
    for verb, name, kw in PREFIX:
        pipe = getattr(pipe, verb)(name, **kw)
    return pipe


def run_recipe(pipe, export: Path, label: str):
    from repro.core import obs

    obs.metrics().reset()
    t0 = time.perf_counter()
    _, report = pipe.write_jsonl(str(export)).execute()
    dt = time.perf_counter() - t0
    probe = sum(sp["dur"] for sp in (report.trace or {}).get("spans", [])
                if sp["kind"] == "plan")
    print(f"{label}: in={report.n_in} out={report.n_out} errors={report.errors} "
          f"seconds={dt:.3f} probe+optimize={probe:.3f}s plan={report.plan}")
    for row in report.per_op:
        print(f"  {row['op']:60s} {row['seconds']:.3f}s {row['in']}->{row['out']}")
    require(report.errors == 0, f"{label}: {report.errors} op errors")
    return report, obs.metrics().snapshot()["counters"]


def read_rows(path: Path) -> list:
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f]


def run_pipeline(corpus: str, arch: str, reduced: bool, seq_len: int) -> list:
    """Phase 3; returns the exported rows."""
    export = OUT / "scored.jsonl"
    pipe = (prefix_pipeline(corpus)
            .filter("lm_perplexity_filter", arch=arch, reduced=reduced, seq_len=seq_len)
            .dedup("document_minhash_deduplicator", streaming="exact", use_kernel=True))
    report, counters = run_recipe(pipe, export, "pipeline")
    require(report.n_out > 0, "pipeline exported no rows")
    rows = read_rows(export)
    require(len(rows) == report.n_out, f"{len(rows)} rows on disk, report says {report.n_out}")
    ppl = [r.get("stats", {}).get("lm_ppl") for r in rows]
    require(all(isinstance(v, float) and math.isfinite(v) for v in ppl),
            "an exported row has no finite lm_ppl")
    dispatches = counters.get("dedup.signature_dispatches_total", 0)
    kernel_spans = [sp for sp in (report.trace or {}).get("spans", [])
                    if sp["kind"] == "kernel_batch"]
    print(f"pipeline: signature dispatches={dispatches:g} kernel spans="
          f"{len(kernel_spans)} lm_ppl min={min(ppl):.6g} max={max(ppl):.6g}")
    require(dispatches > 0, "no MinHash signature dispatch was counted")
    require(len(kernel_spans) == dispatches and all(sp["attrs"].get("kernel") for sp in kernel_spans),
            "a MinHash signature dispatch did not take the kernel path")
    return rows


def check_scorer(rows: list, arch: str, reduced: bool, seq_len: int, n: int) -> None:
    """Phase 4: loss_fn on each row alone vs the op's batched per-row score."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.data.tokenizer import HashWordTokenizer
    from repro.models.model_zoo import build_model
    from repro.ops.model_ops import encode_rows

    gc.collect()  # let phase 3's params go before this phase makes its own
    cfg = get_config(arch, reduced=reduced)
    model = build_model(cfg, remat_policy="none")
    params = model.init_params(jax.random.PRNGKey(0))  # the op's seed
    texts = [r["text"] for r in rows[:n]]
    toks, labels, mask = encode_rows(HashWordTokenizer(cfg.vocab_size), texts, seq_len)
    loss_one = jax.jit(lambda p, t, l, m: model.loss_fn(
        p, {"tokens": t, "labels": l, "loss_mask": m})[0])
    ref = []
    with jax.default_matmul_precision("highest"):
        for i in range(len(texts)):
            ref.append(float(loss_one(params, jnp.asarray(toks[i:i + 1]),
                                      jnp.asarray(labels[i:i + 1]),
                                      jnp.asarray(mask[i:i + 1]))))
    got = [math.log(r["stats"]["lm_ppl"]) for r in rows[:n]]
    diff = np.abs(np.array(got) - np.array(ref))
    print(f"scorer: rows={len(ref)} op loss={[round(g, 6) for g in got]}")
    print(f"scorer: ref loss={[round(r, 6) for r in ref]}")
    print(f"scorer: max |diff|={diff.max():.6g} nats, tolerance {SCORER_TOL_NATS} nats; "
          f"spread of ref losses {np.ptp(ref):.6g} nats")
    require(bool(np.all(diff <= SCORER_TOL_NATS)),
            f"scorer differs from loss_fn by {diff.max():.6g} nats")


def check_dedup(corpus: str, scored_rows: list) -> None:
    """Phase 5: streaming exact vs barrier, kernel path, byte-identical."""
    exports = {}
    for mode in ("exact", "off"):
        exports[mode] = OUT / f"dedup_{mode}.jsonl"
        pipe = prefix_pipeline(corpus).dedup(
            "document_minhash_deduplicator", streaming=mode, use_kernel=True)
        _, counters = run_recipe(pipe, exports[mode], f"dedup[{mode}]")
        if mode == "exact":  # the barrier signs in one call, outside the batcher
            require(counters.get("dedup.signature_dispatches_total", 0) > 0,
                    "dedup[exact]: no signature dispatch counted")
    a, b = (exports[m].read_bytes() for m in ("exact", "off"))
    require(a == b, "streaming exact and barriered dedup exports differ")
    texts = [r["text"] for r in read_rows(exports["exact"])]
    require(texts == [r["text"] for r in scored_rows],
            "the scored pipeline kept different rows than the scorer-free one")
    print(f"dedup: exact == off, {len(a)} bytes, {len(texts)} rows")


def main() -> int:
    import jax

    devices = jax.devices()
    print(f"devices: {devices}")
    print(f"device_kind: {devices[0].device_kind} platform: {devices[0].platform}")
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (first device is {devices[0].platform}); "
              "this check runs only on a TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core.storage import write_jsonl
    from repro.data.synthetic import make_corpus

    print(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    phases = {}

    def phase(name, fn, *args):
        t0 = time.perf_counter()
        result = fn(*args)
        phases[name] = time.perf_counter() - t0
        print(f"phase {name}: {phases[name]:.3f}s memory={device_memory()}")
        return result

    phase("kernel", check_kernel, *KERNEL_SHAPE)
    corpus = str(OUT / "corpus.jsonl")
    phase("corpus", write_jsonl, corpus, make_corpus(N_DOCS, seed=0))
    rows = phase("pipeline", run_pipeline, corpus, ARCH, False, SEQ_LEN)
    phase("scorer", check_scorer, rows, ARCH, False, SEQ_LEN, N_SCORER_ROWS)
    phase("dedup", check_dedup, corpus, rows)
    print(f"phases: {json.dumps({k: round(v, 3) for k, v in phases.items()})}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
