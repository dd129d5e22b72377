"""Seconds of the program's ``BlockWriter.write_block`` (``export.write``), per
1,000 input documents."""
from bench.metrics._program import seconds_per_kdoc


def read(ctx):
    return seconds_per_kdoc(ctx, "export.write")
