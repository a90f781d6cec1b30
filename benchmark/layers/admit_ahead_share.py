"""Admission: the share of the window's admissions that took a slot while
the request before in it still had tokens in a chunk not read
(``LMEngine.stats``: ``admits_ahead`` over ``prefills``, differences of two
reads). On the engine that runs a chunk ahead, a request that a dispatched
chunk ends by length gives its slot up at that dispatch, so the next
prompt's first window rides the chunk right behind it instead of one chunk
later; under a queue nearly every admission should be such a one. An
engine without the counter (a commit that frees a slot only when its
request's last chunk is read) gives None."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("admits_ahead",), (), "prefills")
    return None if got is None else got * 100.0
