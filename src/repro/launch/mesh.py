"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state; `dryrun.py` sets XLA_FLAGS before any jax import.

Single pod:  (16, 16)    axes ("data", "model")  — v5e-256
Multi-pod:   (2, 16, 16) axes ("pod", "data", "model") — 2 pods / 512 chips.
"pod" is pure data-parallel (one cross-pod gradient all-reduce per step);
"data" is FSDP (batch + weight shards); "model" is tensor/expert-parallel.

Every axis is ``Auto``: the step builders place data with
``with_sharding_constraint`` and ``NamedSharding``, which need Auto axes
(``jax.make_mesh`` defaults to Explicit ones).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1x1 mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def make_serve_mesh(tp: int) -> jax.sharding.Mesh:
    """(1, tp) mesh over the first ``tp`` devices, axes ("data", "model") —
    the serving engine's tensor-parallel mesh.  The "model" axis carries the
    KV-head shards of the paged pools and the head-parallel attention; the
    "data" axis is degenerate (continuous batching already packs the batch).

    Works on real chips and on an emulated host mesh alike: run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to develop and CI
    the whole path on CPU.
    """
    devices = jax.devices()
    if tp > len(devices):
        raise ValueError(
            f"tp={tp} needs {tp} devices but only {len(devices)} are visible "
            "(emulate with XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    return jax.make_mesh(
        (1, tp), ("data", "model"), devices=devices[:tp], axis_types=(AxisType.Auto,) * 2
    )


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The batch-sharding axes for this mesh ("pod" folds into DP)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
