"""Kernels, whole decode step with experts: the least time the chip could
take for the traced steps over the time they took. Least is the larger of
bytes over HBM bandwidth (the weights outside the experts once a step, the
experts that were hit at 37.7 MB each, the stored latent rows the kept
rows attended at 2,304 B a layer) and FLOPs over the bf16 peak."""

from . import moe_step


def read(ctx):
    got = moe_step.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    nbytes = moe_step.step_bytes(ctx, got)
    if nbytes is None:
        return None
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                moe_step.step_flops(ctx, got)
                / ctx.peaks["bf16_flops_per_s"])
    return least * 100.0 / got.secs
