"""Kernels, whole decode step: the least time the chip could take for the
traced decode steps over the time they took. Least is the larger of the
bytes a step must read over HBM bandwidth and its FLOPs over the bf16 peak,
both as the configuration's counter counts them (``steps/``: the weights
once a step and the live K/V rows for the GPT-2 block; the weights outside
the experts, the experts that were hit and the stored latent rows attended
for the block with experts). At these sizes bytes bound it."""

from .. import steps


#: a configuration no counter counts fails when its cell is loaded
requires = steps.counter


def read(ctx):
    count = steps.counter(ctx.cell.config)
    got = count.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    nbytes = count.step_bytes(ctx, got)
    if nbytes is None:
        return None
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                count.step_flops(ctx, got) / ctx.peaks["bf16_flops_per_s"])
    return least * 100.0 / got.secs
