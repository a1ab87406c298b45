"""Set-up seconds: process start to the window's open (weights, warm-up,
compiles, the closed loop's first admissions), host clock."""


def read(ctx):
    return ctx.setup_s
