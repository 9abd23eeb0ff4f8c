"""Scheduler: 90th percentile of admission minus submission, from the
engine's own request stamps, over the window's admitted requests."""
import math


def read(ctx):
    waits = [r.admit - r.submit for r in ctx.requests
             if not math.isnan(r.admit)]
    return ctx.pct(waits, 0.90) if waits else None
