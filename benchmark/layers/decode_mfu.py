"""Model, whole decode step: closed-form FLOPs of the kept decode steps of
the traced window over the decode-chunk module's device time times the
chip's bf16 peak (a matmul counts once whatever its precision)."""

from .. import work
from .decode_step_ms import device_seconds_and_steps


def read(ctx):
    got = device_seconds_and_steps(ctx)
    if got is None or not ctx.peaks:
        return None
    w = work.tally(ctx, work.traced_iterations(ctx))
    if w.decode_flops <= 0:
        return None
    return w.decode_flops * 100.0 / (got[0] * ctx.peaks["bf16_flops_per_s"])
