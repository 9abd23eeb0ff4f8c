"""Engine loop: device idle time whose midpoint falls inside one of the
engine's ``serve.*`` phase spans, in the traced interval, per
``serve.decode`` span (one a decode block), in ms."""
import scopes


def read(ctx):
    r = scopes.of(ctx)
    if not r or not r["decode_spans"] or not r["span_idle"]:
        return None
    return 1e3 * sum(r["span_idle"].values()) / r["decode_spans"]
