"""Device time of the engine's prefill-chunk program per execution, in the
traced window (cells that report the median TTFT)."""
from bench.trace import ms_per_execution


def read(ctx):
    return ms_per_execution(ctx, "jit__prefill_impl")
