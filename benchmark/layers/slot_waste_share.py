"""Engine step: the share of slot-steps the window's decode chunks spent
on empty slots or on tokens past a request's end (``LMEngine.stats``,
difference of two reads). The engine computes every slot in every step,
so the base is decode steps times slots; its ``slot_steps`` counts active
slots only, while its ``wasted_slot_steps`` counts the empty ones too."""


def read(ctx):
    a, b = ctx.window.stats_start, ctx.window.stats_end
    base = (b["decode_steps"] - a["decode_steps"]) \
        * int(ctx.cell.config["engine"]["n_slots"])
    if base <= 0:
        return None
    return (b["wasted_slot_steps"] - a["wasted_slot_steps"]) * 100.0 / base
