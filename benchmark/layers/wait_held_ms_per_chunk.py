"""Engine step: the time in ``serving.device_wait`` over which the
process's witness thread woke more than 10 ms past the 5 ms it slept
(``LMEngine.stats["wait_held_s"]``: the host stood still, descheduled or
held out of the interpreter; nothing was judged there) over the window's
chunks. None on an engine without the witness."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("wait_held_s",), (), "chunks")
    return None if got is None else got * 1e3
