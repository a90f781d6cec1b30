"""Admission: the share of the rows the lane offered that held a prompt
token (``LMEngine.stats``: ``lane_tokens`` over ``lane_rows``, differences
of two reads). A lane step offers a fixed window of rows to one prompt, so
a prompt's last window and a prompt shorter than the window leave rows
empty that cost the step the same. None without the counters, and for a
window in which no prompt was admitted."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("lane_tokens",), (), "lane_rows")
    return None if got is None else got * 100.0
