"""Engine step, the family with a held share of experts: the share of the
experts held here that the window's decode steps read (``LMEngine.stats``,
difference of two reads): ``experts_hit`` (distinct held experts with at
least one row, a step and layer, counted inside the jitted step from the
real routing, lane rows included) over decode steps times layers times the
experts held (``num_experts``). An engine without the counter, or another
family's configuration, gives None."""

from ..steps import qwen3_next_block as q3

KEY = "experts_hit"


def read(ctx):
    cfg = ctx.cell.config
    if any(k not in cfg for k in q3.KEYS):
        return None
    hit = q3.per_step(ctx, KEY)
    if hit is None:
        return None
    s = q3.sizes(cfg)
    return hit * 100.0 / (s["layers"] * s["e"])
