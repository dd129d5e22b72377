"""Seconds the host mappers and rule filters spent in the window, from the
program's live per-op entries, per 1,000 input documents."""
from bench.metrics import op_seconds_per_kdoc


def read(ctx):
    return op_seconds_per_kdoc(
        ctx, lambda n: "deduplicator" not in n and "lm_perplexity_filter" not in n)
