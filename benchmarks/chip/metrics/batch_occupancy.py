"""Scheduler: live rows per decode tick over the pool's slots, from the
engine's counters over the window, in %."""


def read(ctx):
    c = ctx.counters
    if not c["decode_steps"]:
        return None
    return 100.0 * c["active_row_steps"] / (c["decode_steps"] * ctx.n_slots)
