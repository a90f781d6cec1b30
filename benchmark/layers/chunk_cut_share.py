"""Engine step: the share of the window's decode chunks whose step count
was cut so that the chunk ends where a decoding request ends by length
while a request waited in the queue (``LMEngine.stats``: ``chunks_cut``
over ``chunks``, differences of two reads, both counted when a chunk is
read). Such a chunk frees its slot at its dispatch with no idle tail, and
the queue's head takes its first window in the chunk right behind; below
the knee, with no queue, nothing is cut. An engine without the counter (a
commit that plans a chunk's length without regard to where its requests
end) gives None."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("chunks_cut",), (), "chunks")
    return None if got is None else got * 100.0
