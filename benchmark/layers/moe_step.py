"""Shared by the readers of the decode step with experts
(``moe_decode_step_ms``, ``moe_decode_mfu``, ``moe_decode_hbm_roofline``,
``expert_gemm_roofline``, ``latent_attn_roofline``): the traced window's
device time and its work, counted from the harness's own records as
``work.py`` counts the GPT-2 block's.

``experts_hit`` is a counter of the whole window (``LMEngine.stats``,
counted inside the jitted step from the real routing); the traced steps
are given the window's mean a step, which a cell that runs with full slots
from its first seconds to its close bears out. A program without the
counter (the parent of the PR that brought it) gives None everywhere."""

from typing import NamedTuple

from .. import flops_glm47 as fg

MODULE = "jit__decode_chunk"
#: a Mosaic custom call as the trace names it (trace_reduce.short_op)
CALL = "[tpu_custom_call]"


class Traced(NamedTuple):
    secs: float          # device seconds of the module
    steps: int           # decode steps it ran
    kept: int            # decode rows whose token a request kept
    attended: int        # stored tokens those rows attended
    prompt: int          # prompt tokens prefilled through the lane
    prompt_attended: int  # stored tokens their rows attended


def traced(ctx):
    """The traced iterations' device time and work, or None."""
    if ctx.trace is None or "num_experts_per_tok" not in ctx.cell.config:
        return None
    secs = ctx.trace.module_seconds(MODULE)
    by_index = {r.index: r for r in ctx.window.requests}
    steps = kept = attended = prompt = prompt_attended = 0
    for it in ctx.window.iterations:
        if not it.traced:
            continue
        steps += it.decode_steps
        for index, before, after in it.progress:
            t = int(by_index[index].prompt.size)
            if before == 0:
                # its prompt went through the lane: row j attended j + 1
                prompt += t
                prompt_attended += t * (t + 1) // 2
                pos0, n = t, after - 1
            else:
                pos0, n = t + before - 1, after - before
            if n > 0:
                kept += n
                attended += n * (pos0 + 1) + n * (n - 1) // 2
    if secs <= 0 or steps <= 0:
        return None
    return Traced(secs, steps, kept, attended, prompt, prompt_attended)


def hit_per_step(ctx):
    """Distinct experts that got a row, a decode step (all expert layers),
    over the whole window; None without the counter."""
    a, b = ctx.window.stats_start, ctx.window.stats_end
    if "experts_hit" not in a or "experts_hit" not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    return (b["experts_hit"] - a["experts_hit"]) / steps


def step_flops(ctx, got):
    cfg = ctx.cell.config
    return got.kept * fg.row_flops(cfg, head=True) \
        + got.prompt * fg.row_flops(cfg, head=False) \
        + (got.attended + got.prompt_attended) * fg.attended_row_flops(cfg)


def latent_bytes(ctx, rows):
    """Bytes of ``rows`` stored tokens, all layers."""
    cfg = ctx.cell.config
    return rows * int(cfg["num_hidden_layers"]) * fg.latent_bytes_per_row(cfg)


def step_bytes(ctx, got):
    """Least bytes the traced steps read: the fixed weights once a step,
    the experts that were hit, the stored tokens the kept rows attended."""
    hit = hit_per_step(ctx)
    if hit is None:
        return None
    cfg = ctx.cell.config
    return got.steps * (fg.fixed_weight_bytes_per_step(cfg)
                        + hit * fg.expert_bytes(cfg)) \
        + latent_bytes(ctx, got.attended + got.prompt_attended)


def kernel_seconds(trace, name):
    """Device seconds of the Mosaic calls named ``name`` inside the
    decode-chunk module."""
    return sum(s for (mod, op), s in trace.ops.items()
               if mod == MODULE and CALL in op and name in op
               ) / max(trace.n_devices, 1)
