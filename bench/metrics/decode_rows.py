"""Mean rows decoded per decode step in the traced window."""


def read(ctx):
    steps = [t.decode_rows for t in ctx.traced_ticks() if t.kind == "decode"]
    return sum(steps) / len(steps) if steps else None
