"""The decode step of the ``qwen3_next`` block (Gated DeltaNet layers with a
recurrent state a slot, grouped-query full attention, a held share of
softmax-routed experts and a gated shared expert), counted from the
harness's own records as ``glm_experts.py`` counts its block's: the traced
window's device time, its decode steps, the kept decode rows and the prompt
tokens prefilled through the lane, and the stored tokens both attended.
Read by the whole step's three metrics through ``steps.counter`` and by the
readers of its kernels (``gdn_state_roofline``, ``gqa_attn_roofline``).

A step's bytes, as the configuration's ``bytes`` block reckons them: every
weight outside the routed experts once, each held expert that got a row
once, each live slot-layer's recurrent state read and written and its
convolution rows read and written, and the K and V rows the kept rows
attended in the full layers. Its FLOPs: each kept decode row and each lane
row through every matrix, the delta rule and the picks of held experts
among its ``num_experts_per_tok``, and each stored token it attended.

``experts_hit``, ``held_picks``, ``picks`` and ``state_rows`` are counters
of the whole window (``LMEngine.stats``, counted inside the jitted step);
the traced steps are given the window's mean a step, which a cell that
runs with full slots from its first seconds to its close bears out. A
program without the counters (the parent of the PR that brought them)
gives None for the bytes and the FLOPs."""

from typing import Any, Dict, NamedTuple

from . import MODULE

#: a Mosaic custom call as the trace names it (trace_reduce.short_op)
CALL = "[tpu_custom_call]"
#: keys of this family alone: the module counts a configuration that has
#: every one
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "full_attention_interval", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size",
        "shared_expert_intermediate_size", "num_hidden_layers", "vocab_size")


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The configuration's sizes under short names."""
    c = config
    s = {"d": int(c["hidden_size"]), "hq": int(c["num_attention_heads"]),
         "hkv": int(c["num_key_value_heads"]), "hd": int(c["head_dim"]),
         "hk": int(c["linear_num_key_heads"]),
         "h": int(c["linear_num_value_heads"]),
         "dk": int(c["linear_key_head_dim"]),
         "dv": int(c["linear_value_head_dim"]),
         "taps": int(c["linear_conv_kernel_dim"]),
         "f": int(c["moe_intermediate_size"]),
         "fs": int(c["shared_expert_intermediate_size"]),
         "e": int(c["num_experts"]), "k": int(c["num_experts_per_tok"]),
         "layers": int(c["num_hidden_layers"]),
         "vocab": int(c["vocab_size"]),
         "router": int(c.get("deployment", {}).get("router_experts",
                                                   c["num_experts"]))}
    s["full"] = s["layers"] // int(c["full_attention_interval"])
    s["lin"] = s["layers"] - s["full"]
    s["channels"] = 2 * s["hk"] * s["dk"] + s["h"] * s["dv"]
    return s


def linear_mixer_params(s) -> int:
    return (s["d"] * (s["channels"] + s["h"] * s["dv"]) + s["d"] * 2 * s["h"]
            + s["taps"] * s["channels"] + 2 * s["h"] + s["dv"]
            + s["h"] * s["dv"] * s["d"])


def full_mixer_params(s) -> int:
    return (s["d"] * s["hq"] * 2 * s["hd"] + 2 * s["d"] * s["hkv"] * s["hd"]
            + s["hq"] * s["hd"] * s["d"] + 2 * s["hd"])


def fixed_weight_bytes_per_step(config: Dict[str, Any]) -> int:
    """Bytes of weights every decode step reads whatever its rows pick:
    the mixers, the router, the shared expert and its gate, the norms, the
    head (the embedding rows gathered for a step's tokens are left out)."""
    s = sizes(config)
    every = s["d"] * s["router"] + 3 * s["d"] * s["fs"] + s["d"] + 2 * s["d"]
    params = (s["lin"] * linear_mixer_params(s)
              + s["full"] * full_mixer_params(s) + s["layers"] * every
              + s["d"] + s["vocab"] * s["d"])
    return params * 4


def expert_bytes(config: Dict[str, Any]) -> int:
    """One routed expert's three matrices, float32."""
    s = sizes(config)
    return 3 * s["d"] * s["f"] * 4


def state_bytes(config: Dict[str, Any]) -> int:
    """One slot-layer's recurrent state, float32."""
    s = sizes(config)
    return s["h"] * s["dk"] * s["dv"] * 4


def conv_bytes(config: Dict[str, Any]) -> int:
    """One slot-layer's convolution rows (the last ``taps - 1`` inputs)."""
    s = sizes(config)
    return (s["taps"] - 1) * s["channels"] * 4


def kv_bytes_per_token(config: Dict[str, Any]) -> int:
    """A stored token's K and V rows, all full layers."""
    s = sizes(config)
    return s["full"] * 2 * s["hkv"] * s["hd"] * 4


def row_flops(config: Dict[str, Any], head: bool) -> float:
    """FLOPs one row costs a step outside the routed experts and its
    attention over stored tokens; ``head`` adds the unembedding."""
    s = sizes(config)
    lin = (linear_mixer_params(s) - s["taps"] * s["channels"]) * 2.0 \
        + 2.0 * s["taps"] * s["channels"] \
        + 7.0 * s["h"] * s["dk"] * s["dv"]      # decay, S^T k, k u^T, S^T q
    full = full_mixer_params(s) * 2.0 \
        + 2.0 * s["hq"] * s["hd"]               # the row's own K/V row
    every = 2.0 * s["d"] * (s["router"] + 3 * s["fs"] + 1)
    total = s["lin"] * lin + s["full"] * full + s["layers"] * every
    if head:
        total += 2.0 * s["d"] * s["vocab"]
    return total


def held_pick_flops(config: Dict[str, Any]) -> float:
    """One pick of a held expert, one row."""
    s = sizes(config)
    return 2.0 * 3 * s["d"] * s["f"]


def attended_row_flops(config: Dict[str, Any]) -> float:
    """FLOPs one stored token costs one attending row, all full layers:
    each query head's score and its sum of the value row."""
    s = sizes(config)
    return 4.0 * s["full"] * s["hq"] * s["hd"]


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


def per_step(ctx, key):
    """A counter of ``LMEngine.stats`` over the window, a decode step; None
    without it."""
    a, b = ctx.window.stats_start, ctx.window.stats_end
    if key not in a or key not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    return (b[key] - a[key]) / steps


def held_share(ctx):
    """Of the window's picks, the share that chose an expert held here;
    None without the counters."""
    a, b = ctx.window.stats_start, ctx.window.stats_end
    if any(k not in a or k not in b for k in ("held_picks", "picks")):
        return None
    picks = b["picks"] - a["picks"]
    return (b["held_picks"] - a["held_picks"]) / picks if picks > 0 else None


def step_flops(ctx, got):
    """0.0 without the counters, which the readers take as nothing read."""
    share = held_share(ctx)
    if share is None:
        return 0.0
    cfg = ctx.cell.config
    rows = got.kept + got.prompt
    return got.kept * row_flops(cfg, head=True) \
        + got.prompt * row_flops(cfg, head=False) \
        + rows * int(cfg["num_experts_per_tok"]) * share \
        * held_pick_flops(cfg) \
        + (got.attended + got.prompt_attended) * attended_row_flops(cfg)


def step_bytes(ctx, got):
    """Least bytes the traced steps read and write: the fixed weights once
    a step, the held experts that were hit, each live slot-layer's state
    and convolution rows read and written, the K and V rows attended."""
    hit = per_step(ctx, "experts_hit")
    states = per_step(ctx, "state_rows")
    if hit is None or states is None:
        return None
    cfg = ctx.cell.config
    return got.steps * (fixed_weight_bytes_per_step(cfg)
                        + hit * expert_bytes(cfg)
                        + states * 2 * (state_bytes(cfg) + conv_bytes(cfg))) \
        + (got.attended + got.prompt_attended) * kv_bytes_per_token(cfg)


def kernel_seconds(trace, name):
    """Device seconds of the Mosaic calls named ``name`` inside the
    decode-chunk module."""
    return sum(s for (mod, op), s in trace.ops.items()
               if mod == MODULE and CALL in op and name in op
               ) / max(trace.n_devices, 1)
