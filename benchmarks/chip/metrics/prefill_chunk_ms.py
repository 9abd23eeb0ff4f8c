"""Prefill program: device time of ``prefill_chunks_batched`` per dispatch
in the traced interval, in ms."""


def read(ctx):
    t = ctx.program_s("jit_prefill_chunks_batched")
    n = ctx.program_calls("jit_prefill_chunks_batched")
    return 1e3 * t / n if t and n else None
