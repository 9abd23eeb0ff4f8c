"""Whole serving step: model FLOPs of every prompt chunk and decode tick of
the traced steps over the traced interval at the chip's peak bf16 FLOP/s, in
%. It bounds every program's share: work moved between programs or onto the
host does not raise it."""


def read(ctx):
    if not ctx.trace:
        return None
    flops = ctx.decode_work()[1] + ctx.prefill_flops()
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peak["flops_per_s"])
