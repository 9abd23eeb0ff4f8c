"""Output tokens stamped inside the window, over the window."""


def read(ctx):
    return ctx.tokens_in_window() / ctx.window_s
