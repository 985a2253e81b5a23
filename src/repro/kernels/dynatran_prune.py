"""Pallas kernel: DynaTran threshold prune + tile-mask emission.

The ASIC's DynaTran module (paper Fig. 7) compares every element of a tile
against tau in parallel and emits a binary mask.  TPU-native version: a VPU
elementwise compare over a VMEM block, fused with the tile-mask reduction
(`any`) that the block-sparse matmul consumes — one pass over HBM.

Block shape (256, 128): last dim 128 = lane width, second-to-last a multiple
of 8 (f32) / 16 (bf16) sublanes; 256x128x4B = 128 KiB per operand block,
comfortably inside v5e VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.policy import resolve_interpret

DEFAULT_BLOCK = (256, 128)


def _kernel(x_ref, tau_ref, out_ref, tile_mask_ref):
    x = x_ref[...]
    tau = tau_ref[0]
    keep = jnp.abs(x) >= tau
    out_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))
    tile_mask_ref[0, 0] = jnp.any(keep)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dynatran_prune(
    x: jax.Array, tau: jax.Array | float, *, block: tuple[int, int] = DEFAULT_BLOCK, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Prune a [M, N] (or [..., M, N], flattened) matrix; returns
    (pruned, tile_mask [M/bm, N/bn] bool)."""
    orig_shape = x.shape
    if x.ndim > 2:
        x = x.reshape(-1, x.shape[-1])
    m, n = x.shape
    bm, bn = block
    bm, bn = min(bm, m), min(bn, n)
    if m % bm or n % bn:
        raise ValueError(f"shape {(m, n)} not divisible by block {(bm, bn)}")
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1)
    grid = (m // bm, n // bn)
    out, tile_mask = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pl.ANY) if False else pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct(grid, jnp.bool_),
        ],
        interpret=resolve_interpret(interpret),
    )(x, tau_arr)
    return out.reshape(orig_shape), tile_mask
