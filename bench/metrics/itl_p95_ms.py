"""95th percentile of the same gaps as ``itl_p50_ms``."""
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx.gaps_s(), 95)
    return None if v is None else 1e3 * v
