"""The MinHash kernel's share of its memory roofline: the bytes its calls in
the traced window must move (``bench/costs.py``, from each call's
shape) over the HBM peak, divided by the device time of the kernel's
program (``jit_minhash_pallas``) in the trace.

A call's shape: its documents (the program's ``kernel:minhash`` span) and
their longest shingle list (from the reference's replay of the documents
that reached dedup, in order; the adapter probe's calls are not the
stream's and are skipped), by the permutations. The shape is the call's
own, not the padded one the kernel runs at: padding is work a call need not
do. No int32 VPU peak is published for v5e, so this is the memory bound
only."""
from bench.costs import minhash_kernel_bytes
from bench.trace import module_time


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    secs = module_time(tr, "jit_minhash_pallas")
    if not secs:
        return None
    p = next(s for s in ctx["cfg"]["process"] if "deduplicator" in s["name"])["num_permutations"]
    shingles = ctx["checked"]["dedup_shingles"]
    t0 = ctx["snaps"][0]["wall"]
    # the adapter probe's calls run on probe rows, not on the stream's
    probe = [(s["t0"], s["t0"] + s["dur"]) for s in ctx["spans"] if s["name"] == "plan:optimize"]
    total, start = 0, 0
    for sp in sorted((s for s in ctx["spans"] if s["kind"] == "kernel_batch"
                      and not any(a <= s["t0"] <= b for a, b in probe)), key=lambda s: s["t0"]):
        n = sp["attrs"]["docs"]
        if sp["t0"] >= t0:
            s_max = max(shingles[start:start + n], default=1)
            total += minhash_kernel_bytes(n, s_max, p)
        start += n
    if not total:
        return None
    return 100.0 * total / ctx["peaks"]["hbm_bytes_per_s"] / secs
