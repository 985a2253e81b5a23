"""Device time of the engine's decode-step program per execution, in the
traced window."""
from bench.trace import ms_per_execution


def read(ctx):
    return ms_per_execution(ctx, "jit__decode_impl")
