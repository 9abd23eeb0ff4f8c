"""Decode attention (SwiftKV, ``decode_attention``): self time of the
decode-block programs' ops in the ``attention`` scope, in the traced
interval, per decode tick those steps issued, in ms."""
import scopes


def read(ctx):
    t = scopes.scope_s(ctx, "attention")
    ticks = ctx.decode_work()[0]
    return 1e3 * t / ticks if t and ticks else None
