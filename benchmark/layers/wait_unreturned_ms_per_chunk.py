"""Engine step: the time ``serving.device_wait`` went on after the
process's witness thread had seen the awaited arrays ready
(``LMEngine.stats["wait_unreturned_s"]``, less held time; a lower bound,
since the arrays turned ready up to a 5 ms look before) over the window's
chunks. A chunk whose wait returns as soon as it is ready adds 0. None on
an engine without the witness."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("wait_unreturned_s",), (), "chunks")
    return None if got is None else got * 1e3
