"""Share of the scorer's positions that are padding: padded positions and
rows over all positions of the batches the step scored in the traced window
(the ``scorer.tokens_scored`` and ``scorer.tokens_padded`` counts of each
``scorer.step``)."""
from bench.metrics._program import annotations


def read(ctx):
    step = (annotations(ctx) or {}).get("scorer.step")
    if not step or "scorer.tokens_padded" not in step:
        return None
    total = step["scorer.tokens_scored"] + step["scorer.tokens_padded"]
    return 100.0 * step["scorer.tokens_padded"] / total if total else None
