"""Engine step: the unready time of the waits in which the chunk queued
behind the awaited one was seen ready within two 5 ms looks of it, with
the host awake through the wait (``LMEngine.stats["wait_delivered_late_s"]``:
the device had run both, so the awaited chunk's completion came late),
over the window's chunks. None on an engine without the witness."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("wait_delivered_late_s",), (), "chunks")
    return None if got is None else got * 1e3
