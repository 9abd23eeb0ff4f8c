"""90th percentile of the time to first token, from the due time, over every
request due in the window (a failed one counts as infinite)."""


def read(ctx):
    return ctx.pct([r.ttft for r in ctx.requests], 0.90)
