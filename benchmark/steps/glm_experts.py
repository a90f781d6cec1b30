"""The decode step of the ``glm4_moe_lite`` block (latent attention, routed
experts; ``flops_glm47.py``'s closed forms), counted from the harness's own
records as ``gpt2_block.py`` counts the GPT-2 block's: the traced window's
device time, its decode steps, the kept decode rows and the prompt tokens
prefilled through the lane, and the stored tokens both attended. Read by the
whole step's three metrics through ``steps.counter`` and by the readers of
its kernels (``expert_gemm_roofline``, ``latent_attn_roofline``).

``experts_hit`` is a counter of the whole window (``LMEngine.stats``,
counted inside the jitted step from the real routing); the traced steps
are given the window's mean a step, which a cell that runs with full slots
from its first seconds to its close bears out. A program without the
counter (the parent of the PR that brought it) gives None for the bytes."""

from typing import NamedTuple

from .. import flops_glm47 as fg
from . import MODULE

#: a Mosaic custom call as the trace names it (trace_reduce.short_op)
CALL = "[tpu_custom_call]"
#: what ``flops_glm47.sizes`` reads
KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
        "num_hidden_layers", "vocab_size")


class Traced(NamedTuple):
    secs: float          # device seconds of the module
    steps: int           # decode steps it ran
    kept: int            # decode rows whose token a request kept
    attended: int        # stored tokens those rows attended
    prompt: int          # prompt tokens prefilled through the lane
    prompt_attended: int  # stored tokens their rows attended


def traced(ctx):
    """The traced iterations' device time and work, or None (an untraced
    run; another family's configuration, for the kernels' readers)."""
    if ctx.trace is None or any(k not in ctx.cell.config for k in KEYS):
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
