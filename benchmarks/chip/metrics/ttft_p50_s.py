"""Median time to first token, from each request's due time, over every
request due in the window (a failed one counts as infinite)."""


def read(ctx):
    return ctx.pct([r.ttft for r in ctx.requests], 0.50)
