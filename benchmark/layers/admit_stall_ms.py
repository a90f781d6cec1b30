"""Admission: wall time of ``serving.admit`` an admitted request. The
engine admits between two decode chunks, so every running stream stands
still for it: the batch-1 prefill on the device, its dispatch, the host's
preparation and the first token's readback."""

from .step_stats import per


def read(ctx):
    got = per(ctx, ("admit_s",), (), "prefills")
    return None if got is None else got * 1e3
