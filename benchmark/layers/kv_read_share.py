"""Engine step: the share of the K/V store's rows the window's decode
steps were asked to read (``LMEngine.stats``, difference of two reads):
``kv_rows_attended`` over decode steps times slots times ``max_len``. The
engine counts, a chunk at a time, each active slot's position at each
step rounded up to the attention kernel's blocks; an empty slot counts
nothing. An engine without the counter (a commit whose decode step reads
the whole store) gives None."""

KEY = "kv_rows_attended"


def read(ctx):
    a, b = ctx.window.stats_start, ctx.window.stats_end
    if KEY not in a or KEY not in b:
        return None
    eng = ctx.cell.config["engine"]
    base = (b["decode_steps"] - a["decode_steps"]) \
        * int(eng["n_slots"]) * int(eng["max_len"])
    if base <= 0:
        return None
    return (b[KEY] - a[KEY]) * 100.0 / base
