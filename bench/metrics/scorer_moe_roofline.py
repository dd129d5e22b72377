"""The held experts' grouped matmul against the chip's bf16 peak: the
assignments the step's experts computed at scored positions in the traced
window (the ``scorer.moe_rows`` counts of the ``scorer.step`` annotations),
each ``6 * d_model * expert_width`` FLOPs (``bench/costs_granite4h.py``),
over the device time of the grouped-matmul kernel in the step's program
times the peak. The trace summary's ``ops`` files the kernel under
``jit_score_one/custom-call`` where an event is named by its HLO text, and
under ``jit_score_one/gmm`` (the instruction's name) where it is not; the
two never hold the same event. Rows the kernel runs for padded positions, and the partial row tiles
at group edges, count as time and not as work.

The reader assumes the grouped matmul is the step's only custom call: a
second kernel in ``jit_score_one`` (the unwired Pallas ``ssd_scan``, once
wired) would be counted as its time. A program without the counts, or a
trace without that op among its largest, leaves nothing to read."""
from bench.costs_granite4h import expert_row_flops
from bench.metrics._program import annotations

OPS = ("jit_score_one/custom-call", "jit_score_one/gmm")


def read(ctx):
    sc = ctx["cfg"].get("scorer")
    step = (annotations(ctx) or {}).get("scorer.step")
    ops = dict((ctx.get("trace") or {}).get("ops", []))
    kernel_s = sum(ops.get(op, 0.0) for op in OPS)
    if not sc or not step or not step.get("scorer.moe_rows") or not kernel_s:
        return None
    flops = step["scorer.moe_rows"] * expert_row_flops(sc)
    return 100.0 * flops / (kernel_s * ctx["peaks"]["bf16_flops_per_s"])
