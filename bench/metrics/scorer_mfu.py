"""The scorer step's share of the chip's bf16 peak: forward FLOPs per token
of the configured model (``flops_per_token`` of the reference the scorer
block names) times the real, unpadded tokens of the rows scored in the
traced window, over the device time of the step's program
(``jit_score_one``) in the trace times the peak. Padded positions count as
time and not as work."""
from bench.reference import scorer_reference
from bench.trace import module_time


def read(ctx):
    sc = ctx["cfg"].get("scorer")
    tokens = ctx["checked"].get("window_tokens")
    tr = ctx.get("trace")
    secs = module_time(tr, "jit_score_one") if tr else None
    if not sc or not tokens or not secs:
        return None
    flops = scorer_reference(sc).flops_per_token(sc) * tokens
    return 100.0 * flops / (secs * ctx["peaks"]["bf16_flops_per_s"])
