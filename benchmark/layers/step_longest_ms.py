"""Engine step: the longest iteration of the window that used no
executable for the first time, from the engine's own records
(``LMEngine.slowest_steps`` and ``recent_steps``) by ordinal: an ordinary
one is an admission's prefill plus a chunk, a stalled one reads seconds
(PERF.md §7a), and its record says which phase stood still."""


def read(ctx):
    a, b = ctx.window.stats_start, ctx.window.stats_end
    engine = getattr(ctx.adapter, "engine", None)
    if "iterations" not in a or "iterations" not in b \
            or not hasattr(engine, "slowest_steps"):
        return None
    walls = [r["wall_s"]
             for r in engine.slowest_steps() + engine.recent_steps()
             if a["iterations"] < r["iteration"] <= b["iterations"]
             and not r["first_use"]]
    return max(walls) * 1e3 if walls else None
