"""Load generator: 95th percentile of submit time minus due time. The
harness is one thread, so this is how long arrivals waited for an
iteration to end."""

from ..stats import percentile


def read(ctx):
    vals = [(r.submit_s - r.due_s) * 1e3 for r in ctx.window.requests
            if r.submit_s is not None]
    return percentile(vals, 95.0)
