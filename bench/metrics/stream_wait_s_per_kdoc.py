"""Seconds the stream's consumer waited on the prefetch thread for the next
decoded input block (``stream.wait``), per 1,000 input documents."""
from bench.metrics._program import seconds_per_kdoc


def read(ctx):
    return seconds_per_kdoc(ctx, "stream.wait")
