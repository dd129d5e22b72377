"""Seconds the dedup stage spent shingling its input blocks (``dedup.shingle``:
``SignatureBatcher.add_block`` on each block), per 1,000 input documents."""
from bench.metrics._program import seconds_per_kdoc


def read(ctx):
    return seconds_per_kdoc(ctx, "dedup.shingle")
