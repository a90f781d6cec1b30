"""The decode step of the GPT-2 block (``flops.py``'s closed forms), counted
from the harness's own records by ``work.tally``: the traced window's device
time, its decode steps, the FLOPs of the slot-steps whose token a request
kept, and the bytes a step must read: the weights once and the live K/V
rows of those slot-steps. The prompts' rows that ride the steps through the
lane are in the time and in neither count."""

from typing import Any, NamedTuple

from .. import flops, work
from . import MODULE

#: what ``flops.model_dims`` reads
KEYS = ("n_embd", "n_layer", "n_head", "n_inner", "vocab_size",
        "n_positions")


class Traced(NamedTuple):
    secs: float          # device seconds of the module
    steps: int           # decode steps it ran
    work: Any            # work.Work of the traced iterations


def traced(ctx):
    """The traced iterations' device time and work, or None."""
    if ctx.trace is None:
        return None
    secs = ctx.trace.module_seconds(MODULE)
    w = work.tally(ctx, work.traced_iterations(ctx))
    if secs <= 0 or w.decode_steps <= 0:
        return None
    return Traced(secs, w.decode_steps, w)


def step_flops(ctx, got):
    return got.work.decode_flops


def step_bytes(ctx, got):
    cfg = ctx.cell.config
    return got.steps * flops.weight_bytes_per_step(cfg) \
        + got.work.decode_kv_rows * flops.kv_bytes_per_token(cfg)
