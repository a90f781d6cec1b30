"""Engine step: device time of the decode-chunk module's executions in the
traced window over the decode steps they ran."""

from .. import work

MODULE = "jit__decode_chunk"


def device_seconds_and_steps(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.trace.module_seconds(MODULE)
    steps = work.tally(ctx, work.traced_iterations(ctx)).decode_steps
    if secs <= 0 or steps <= 0:
        return None
    return secs, steps


def read(ctx):
    got = device_seconds_and_steps(ctx)
    return None if got is None else got[0] * 1e3 / got[1]
