"""Engine step: the decode chunk as the host sees it: seconds blocked in
``serving.decode_wait`` (the readback of the chunk's tokens) over the
decode steps of the window, every chunk of it. Beside ``decode_step_ms``
(device time of the traced chunks) the difference is what of the chunk had
run before the host began to wait, and the copy to the host."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("decode_wait_s",), (), "decode_steps")
    return None if got is None else got * 1e3
