"""90th percentile, over every request due in the window, of the time from
when the request was due to its first output token being on the host: the
queue for a slot on top of the engine's own latency. A request with no
token when the wait ends counts as missing."""

import math

from ..stats import percentile


def read(ctx):
    vals = [(r.token_s[0] - r.due_s) * 1e3 if r.token_s else math.inf
            for r in ctx.window.requests]
    return percentile(vals, 90.0)
