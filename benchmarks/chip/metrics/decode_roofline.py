"""Decode program: the least time its ticks need on this chip (per tick the
larger of model FLOPs over peak FLOP/s and needed bytes over peak bandwidth:
bf16 weights once, each live row's keys and values, the new rows' writes)
over the decode-block programs' device time, in %."""


def read(ctx):
    t = ctx.program_s("jit_block")
    ticks, _, bound, _ = ctx.decode_work()
    return 100.0 * bound / t if t and ticks else None
