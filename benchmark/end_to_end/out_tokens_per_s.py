"""Output tokens a second: the tokens stamped inside the window, of every
request, over the window's seconds. What the operator of a full engine
pays the chip for. Counted from the harness's own stamps; a token that
appears while the backlog drains after the close is not counted. Below a
cell's knee this is the offered load, so only cells over theirs list it."""


def read(ctx):
    w = ctx.window
    n = sum(1 for r in w.requests for t in r.token_s if t <= w.seconds)
    return n / w.seconds if n else None
