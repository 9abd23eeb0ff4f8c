"""Decode matmuls (``linear``, ``_unembed``): the least time the traced
ticks' projections and output head need on this chip (bf16 weights read
once a tick, FLOPs of the live rows) over the device time of the ``qkv``,
``attn_out``, ``mlp`` and ``lm_head`` scopes, in %."""
import scopes


def read(ctx):
    t = scopes.scope_s(ctx, *scopes.MATMUL_SCOPES)
    if not t:
        return None
    rl, m = ctx.roofline, ctx.model
    bound = 0.0
    for rows in scopes.tick_rows(ctx):
        pf, pb = rl.projection(m, len(rows))
        hf, hb = rl.head(m, len(rows))
        bound += rl.bound_seconds(pf + hf, pb + hb, ctx.peak)[0]
    return 100.0 * bound / t if bound else None
