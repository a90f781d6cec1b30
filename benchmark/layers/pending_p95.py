"""Admission: 95th percentile of ``LMEngine.pending()`` (queued plus
active) read after every iteration of the window."""

from ..stats import percentile


def read(ctx):
    vals = [it.pending_after for it in ctx.window.iterations
            if it.in_window]
    return percentile(vals, 95.0)
