"""Median of every gap between consecutive output tokens of one request,
both emitted in the measured window, over all requests."""
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx.gaps_s(), 50)
    return None if v is None else 1e3 * v
