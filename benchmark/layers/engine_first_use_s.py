"""Engine executables: wall of the dispatch calls that used an executable
for the first time (a prefill bucket, a decode chunk length), all of them
in set-up: compilation on a cold cache, loading from it on a warm one."""


def read(ctx):
    return ctx.window.stats_start.get("first_use_s")
