"""Decode program: device time of the decode-block programs in the traced
steps, per decode tick those steps issued, in ms."""


def read(ctx):
    t = ctx.program_s("jit_block")
    ticks = ctx.decode_work()[0]
    return 1e3 * t / ticks if t and ticks else None
