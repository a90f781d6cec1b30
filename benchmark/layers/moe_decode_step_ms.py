"""Engine step, expert model: device time of the decode-chunk module's
executions in the traced window over the decode steps they ran (lane
windows ride inside the steps, so this is a step as the streams meet it)."""

from . import moe_step


def read(ctx):
    got = moe_step.traced(ctx)
    return None if got is None else got.secs * 1e3 / got.steps
