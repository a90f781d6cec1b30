"""Process start to the window's start: weights made on the device from
the seed, the cell's programs compiled or loaded from the cache, one warm
call of each."""


def read(ctx):
    return ctx.setup_s
