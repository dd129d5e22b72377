"""Seconds the scorer spent tokenizing and padding its batches on the host
(``scorer.encode``), per 1,000 input documents."""
from bench.metrics._program import seconds_per_kdoc


def read(ctx):
    return seconds_per_kdoc(ctx, "scorer.encode")
