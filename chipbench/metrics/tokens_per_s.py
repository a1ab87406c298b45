"""Output tokens returned in the window over the window's seconds."""
from cbench import derive


def read(ctx):
    t0, t1 = ctx.window
    return derive.tokens_in_window(ctx) / (t1 - t0)
