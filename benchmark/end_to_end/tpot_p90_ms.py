"""90th percentile over requests of (last token - first token) / (output
tokens - 1): the mean gap a reader of the stream feels, stalls by other
requests' prefills included. An unfinished request counts as missing."""

import math

from ..stats import percentile


def read(ctx):
    vals = []
    for r in ctx.window.requests:
        if r.max_new < 2:
            continue
        if not r.finished:
            vals.append(math.inf)
            continue
        vals.append((r.token_s[-1] - r.token_s[0]) * 1e3 / (r.max_new - 1))
    return percentile(vals, 90.0)
