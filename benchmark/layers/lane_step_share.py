"""Admission: the share of the window's decode steps that carried a window
of prompt rows (``LMEngine.stats``: ``lane_steps`` over ``decode_steps``,
differences of two reads). A lane step reads the weights once for the
decode rows and the prompt rows together, and costs the streams the rows'
time on top; this is how often they meet one. An engine without the
counter (one that prefills a whole prompt between two chunks) gives
None."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("lane_steps",), (), "decode_steps")
    return None if got is None else got * 100.0
