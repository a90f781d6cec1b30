"""Admission: mean time from ``submit`` to the slot, over the requests
admitted in the window (``LMEngine.stats``: ``admission_wait_s`` over
``prefills``). It moves ``ttft_p90_ms``, which is held back (PERF.md §2), so
this reader has no entry in ``BENCHMARK.json`` yet."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("admission_wait_s",), (), "prefills")
    return None if got is None else got * 1e3
