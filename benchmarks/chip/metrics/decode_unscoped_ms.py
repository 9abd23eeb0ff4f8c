"""Decode program: self time of the decode-block programs' ops outside
every named scope (the compiler's copies and converts, the layer scan's
slicing and stacking of the cache), in the traced interval, per decode
tick those steps issued, in ms."""
import scopes


def read(ctx):
    t = scopes.scope_s(ctx, scopes.UNSCOPED)
    ticks = ctx.decode_work()[0]
    return 1e3 * t / ticks if t and ticks else None
