"""Model: device time of the prefill modules in the traced window over the
prompt tokens they prefilled, per thousand (padded bucket tokens are not
counted as work)."""

from .. import work

MODULE = "jit__prefill_admit"


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.trace.module_seconds(MODULE)
    toks = sum(work.tally(ctx, work.traced_iterations(ctx)).prefills)
    if secs <= 0 or toks <= 0:
        return None
    return secs * 1e3 / (toks / 1000.0)
