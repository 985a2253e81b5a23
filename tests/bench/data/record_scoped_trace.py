"""Record ``scoped_steps.xplane.pb``: four calls of a jitted step whose ops
sit in two named scopes (``proj``, ``mlp``), each call inside nested
``bench.step`` / ``engine.*`` host spans with host work between them, all
in a ``bench.window`` span.

    python tests/bench/data/record_scoped_trace.py <out.xplane.pb>

Run it where a TPU is attached: the reduction reads TPU device planes.
Per call the host spins 5 ms in ``engine.prepare`` (outside its nested
``engine.spill``), 10 ms in ``engine.spill``, 5 ms in ``engine.emit``,
3 ms in ``bench.step`` outside ``engine.step``, 3 ms in ``bench.poll`` and
5 ms under no span, and the device is idle meanwhile.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@jax.jit
def step(w, x):
    with jax.named_scope("proj"):
        h = jax.lax.optimization_barrier(x @ w)  # its own fusion, not the next scope's
    with jax.named_scope("mlp"):
        return jnp.tanh(h) @ w.T


def spin(seconds: float) -> None:
    """Host work that keeps the thread busy (a sleep may overshoot)."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def record(out: Path) -> None:
    w = jnp.full((1024, 1024), 0.01, jnp.bfloat16)
    x = jnp.ones((256, 1024), jnp.bfloat16)
    step(w, x).block_until_ready()  # compiled before the trace
    log_dir = tempfile.mkdtemp(prefix="scoped-trace-")
    jax.profiler.start_trace(log_dir)
    with TraceAnnotation("bench.window"):
        for _ in range(4):
            with TraceAnnotation("bench.step"):
                with TraceAnnotation("engine.step"):
                    with TraceAnnotation("engine.prepare"):
                        spin(0.005)
                        with TraceAnnotation("engine.spill"):
                            spin(0.010)
                    with TraceAnnotation("engine.dispatch"):
                        y = step(w, x)
                    with TraceAnnotation("engine.wait"):
                        y.block_until_ready()
                    with TraceAnnotation("engine.emit"):
                        spin(0.005)
                spin(0.003)
            with TraceAnnotation("bench.poll"):
                spin(0.003)
            spin(0.005)
    jax.profiler.stop_trace()
    (found,) = Path(log_dir).rglob("*.xplane.pb")
    shutil.copy(found, out)
    shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    record(Path(sys.argv[1]))
