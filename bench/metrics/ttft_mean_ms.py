"""Mean, over every request sent in the measured window, of its first
token's host time minus its send time; requests are followed past the
close until their first token."""


def read(ctx):
    ttfts = ctx.ttfts_s()
    return 1e3 * sum(ttfts) / len(ttfts) if ttfts else None
