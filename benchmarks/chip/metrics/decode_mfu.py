"""Decode step: model FLOPs of the live rows' ticks, attention at their cache
lengths included, over the decode-block programs' device time at the chip's
peak bf16 FLOP/s, in %."""


def read(ctx):
    t = ctx.program_s("jit_block")
    ticks, flops, _, _ = ctx.decode_work()
    return 100.0 * flops / (t * ctx.peak["flops_per_s"]) if t and ticks \
        else None
