"""Device time of the engine's prefill-chunk program per execution, in the
traced window: ``prefill_chunk_ms`` of the cells that report the mean
TTFT, which it moves."""
from bench.trace import ms_per_execution


def read(ctx):
    return ms_per_execution(ctx, "jit__prefill_impl")
