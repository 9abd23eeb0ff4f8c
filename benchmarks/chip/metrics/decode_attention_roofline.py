"""Decode attention: the least time the traced ticks' attention needs on
this chip (per tick, the live rows' attention FLOPs and key/value bytes at
their own context lengths, over peak FLOP/s or peak bandwidth, whichever
is slower) over the device time of the ``attention`` scope, in %."""
import scopes


def read(ctx):
    t = scopes.scope_s(ctx, "attention")
    if not t:
        return None
    rl, m = ctx.roofline, ctx.model
    bound = 0.0
    for rows in scopes.tick_rows(ctx):
        fl = by = 0.0
        for p in rows:
            f, b = rl.attention(m, [p])
            fl, by = fl + f, by + b
        bound += rl.bound_seconds(fl, by, ctx.peak)[0]
    return 100.0 * bound / t if bound else None
