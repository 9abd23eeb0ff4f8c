"""90th percentile over requests of the time per output token after the
first: (last token's time - first token's time) / (tokens - 1), in ms. The
first and last tokens of a request are stamped at host syncs."""


def read(ctx):
    return 1e3 * ctx.pct([r.tpot for r in ctx.requests], 0.90)
