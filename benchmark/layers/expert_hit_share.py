"""Engine step, expert model: the share of the routed experts the window's
decode steps read (``LMEngine.stats``, difference of two reads):
``experts_hit`` (distinct experts with at least one row, a step and expert
layer, counted inside the jitted step from the real routing, lane rows
included) over decode steps times expert layers times experts. An engine
without the counter gives None."""

KEY = "experts_hit"


def read(ctx):
    a, b = ctx.window.stats_start, ctx.window.stats_end
    cfg = ctx.cell.config
    if KEY not in a or KEY not in b or "n_routed_experts" not in cfg:
        return None
    layers = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    base = (b["decode_steps"] - a["decode_steps"]) * layers \
        * int(cfg["n_routed_experts"])
    if base <= 0:
        return None
    return (b[KEY] - a[KEY]) * 100.0 / base
