"""Process-level runtime setup shared by the entry points: the persistent
compilation cache and the device report.

``enable_compile_cache`` is called by ``chip_smoke.py``,
``repro.launch.serve`` and ``benchmarks/run.py`` before they compile.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
nothing else is set; otherwise the cache lives at ``<repo>/.jax_cache`` —
a fixed path, since the path is part of what makes a later run hit it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Switch JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def device_info() -> dict:
    """The device JAX runs on, as it reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
