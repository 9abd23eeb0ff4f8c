"""Set-up: process start to the window's start (loading, weights, warm-up,
compiles or compile-cache reads)."""


def read(ctx):
    return ctx.setup_s
