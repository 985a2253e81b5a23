"""Output tokens emitted in the measured window per second of the window."""


def read(ctx):
    return ctx.tokens_in_window() / ctx.window_s
