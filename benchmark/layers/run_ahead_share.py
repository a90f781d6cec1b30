"""Engine step: the share of the window's decode chunks that were
dispatched while the chunk before them was still unread
(``LMEngine.stats``: ``chunks_ahead`` over ``chunks``, differences of two
reads, both counted when a chunk is read). Such a chunk is queued on the
device when the one before it ends, so the readback's tail and the host's
work between two chunks cost the streams nothing; the first chunk after an
idle spell has nothing to be queued behind. An engine without the counter
(a commit that reads every chunk before it dispatches the next) gives
None."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("chunks_ahead",), (), "chunks")
    return None if got is None else got * 100.0
