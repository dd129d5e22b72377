"""Seconds of the dedup stage's signature dispatches (``dedup.sign``: pack,
kernel, wait, signatures on the host), per 1,000 input documents."""
from bench.metrics._program import seconds_per_kdoc


def read(ctx):
    return seconds_per_kdoc(ctx, "dedup.sign")
