"""Kernels (XLA's fusions of the decode step): the least time the chip
could take for the traced decode steps over the time they took. Least is
the larger of bytes over HBM bandwidth — each step reads the weights once
and the live K/V rows of the slots whose token is kept — and FLOPs over
the bf16 peak. At these sizes bytes bound it."""

from .. import flops, work
from .decode_step_ms import device_seconds_and_steps


def read(ctx):
    got = device_seconds_and_steps(ctx)
    if got is None or not ctx.peaks:
        return None
    secs, steps = got
    w = work.tally(ctx, work.traced_iterations(ctx))
    cfg = ctx.cell.config
    nbytes = steps * flops.weight_bytes_per_step(cfg) \
        + w.decode_kv_rows * flops.kv_bytes_per_token(cfg)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                w.decode_flops / ctx.peaks["bf16_flops_per_s"])
    return least * 100.0 / secs
