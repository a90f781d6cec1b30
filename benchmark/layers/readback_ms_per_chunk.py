"""Engine step: the copy of a chunk's ready arrays to the host
(``serving.readback``, inside ``serving.decode_wait`` after
``serving.device_wait``: the chunk's tokens, a family's routing counts, a
quality hook's confidences, in one ``device_get``) over the window's
chunks, every one: a round trip, not the bytes. None on an engine without
the phase."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("readback_s",), (), "chunks")
    return None if got is None else got * 1e3
