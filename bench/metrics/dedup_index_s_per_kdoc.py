"""Seconds the dedup stage spent indexing flushed super-batches (``dedup.index``:
band keys, ``np.unique``, the LSH band index, the Jaccard verify of each
candidate nested in it as ``dedup.verify``, union-find, keep-first), per 1,000
input documents."""
from bench.metrics._program import seconds_per_kdoc


def read(ctx):
    return seconds_per_kdoc(ctx, "dedup.index")
