"""Seconds from the scorer step's call until its losses are on the host
(``scorer.step``), per 1,000 input documents."""
from bench.metrics._program import seconds_per_kdoc


def read(ctx):
    return seconds_per_kdoc(ctx, "scorer.step")
