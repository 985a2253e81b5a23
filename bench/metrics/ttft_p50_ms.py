"""Median, over every request sent in the measured window, of its first
token's host time minus its send time; requests are followed past the
close until their first token."""
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx.ttfts_s(), 50)
    return None if v is None else 1e3 * v
