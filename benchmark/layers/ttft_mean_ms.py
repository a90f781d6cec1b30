"""Admission: the mean, over every request due in the window, of due time
to first token — the steadier statistic beside ``ttft_p90_ms``, which
counts every request and still rises with the tail. A request with no
token when the wait ends makes the mean infinite, and the metric is then
missing from the line."""

import math


def read(ctx):
    vals = [(r.token_s[0] - r.due_s) * 1e3 if r.token_s else math.inf
            for r in ctx.window.requests]
    return sum(vals) / len(vals) if vals else None
