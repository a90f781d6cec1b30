"""Model, whole decode step with experts: closed-form FLOPs of the traced
window's kept decode rows and prefilled prompt tokens
(``flops_glm47.py``; a matmul counts once whatever its precision) over the
decode-chunk module's device time times the chip's bf16 peak."""

from . import moe_step


def read(ctx):
    got = moe_step.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    flops = moe_step.step_flops(ctx, got)
    if flops <= 0:
        return None
    return flops * 100.0 / (got.secs * ctx.peaks["bf16_flops_per_s"])
