"""Engine loop: programs launched per token emitted, from the engine's
counters over the window."""


def read(ctx):
    n = ctx.tokens_emitted()
    return ctx.counters["dispatches"] / n if n else None
