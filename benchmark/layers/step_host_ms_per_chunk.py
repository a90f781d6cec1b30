"""Engine step: the part of an iteration in which the host is not blocked
on the device, a decode dispatch: ``serving.step`` less
``serving.decode_wait`` and ``serving.first_token_wait``, over the
window's chunks (every one, traced or not). It holds the retire loop,
``_admit``'s host work and the enqueue of every program; the harness's
own work between two steps is outside it."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("step_s",), ("decode_wait_s", "first_token_wait_s"),
              "chunks")
    return None if got is None else got * 1e3
