"""Model, whole decode step: closed-form FLOPs of the traced window's work
as the configuration's counter counts them (``steps/``; a matmul counts once
whatever its precision) over the decode-chunk module's device time times
the chip's bf16 peak. The one whole-step share of a peak: every cell
reports it, so a kernel taken off the path stays bounded by it."""

from .. import steps


#: a configuration no counter counts fails when its cell is loaded
requires = steps.counter


def read(ctx):
    count = steps.counter(ctx.cell.config)
    got = count.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    flops = count.step_flops(ctx, got)
    if flops <= 0:
        return None
    return flops * 100.0 / (got.secs * ctx.peaks["bf16_flops_per_s"])
