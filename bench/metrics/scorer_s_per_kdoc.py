"""Seconds of the scorer op (tokenize, pad, step) in the window, per 1,000
input documents."""
from bench.metrics import op_seconds_per_kdoc


def read(ctx):
    return op_seconds_per_kdoc(ctx, lambda n: n == "lm_perplexity_filter")
