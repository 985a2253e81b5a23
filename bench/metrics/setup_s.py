"""Process start to window open: imports, device start, weights, compile or
cache load, warm-up, cached documents and the closed loop's lead-in."""


def read(ctx):
    return ctx.setup_s
