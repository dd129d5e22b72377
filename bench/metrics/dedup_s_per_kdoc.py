"""Seconds of the dedup op in the window (shingling, signature dispatch,
LSH index, union-find; presign work included), per 1,000 input documents."""
from bench.metrics import op_seconds_per_kdoc


def read(ctx):
    return op_seconds_per_kdoc(ctx, lambda n: "deduplicator" in n)
