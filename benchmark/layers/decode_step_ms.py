"""Engine step: device time of the decode-chunk module's executions in the
traced window over the decode steps they ran. The prompts' windows ride
inside the steps, so this is a step as the streams meet it. The block's
counter (``steps/``) is chosen by the configuration's keys."""

from .. import steps


#: a configuration no counter counts fails when its cell is loaded
requires = steps.counter


def read(ctx):
    got = steps.counter(ctx.cell.config).traced(ctx)
    return None if got is None else got.secs * 1e3 / got.steps
