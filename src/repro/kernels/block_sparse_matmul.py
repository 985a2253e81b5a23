"""Pallas kernel: tiled matmul with (a) selectable dataflow (grid order) and
(b) tile-mask skipping — the TPU adaptation of AccelTran's tiled matmul +
pre-compute-sparsity datapath (DESIGN.md §3).

* Tiling: (bm, bk) x (bk, bn) MXU-aligned blocks, f32 accumulation in the
  output block across the k grid dimension (k innermost = the paper's
  [b,i,j,k] dataflow; `dataflow="kij"` moves k outermost to demonstrate the
  energy-relevant reuse difference — same result, different DMA pattern).
* Skipping: the paper ANDs operand masks so only mutually-effectual work
  runs.  Here a tile pair is skipped (`@pl.when`) iff either operand tile is
  dead in its *tile mask* (all |elements| < tau) — skipping both the MXU
  issue and, on real hardware, the HBM->VMEM DMA for that tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.policy import resolve_interpret

DEFAULT_BLOCK = (128, 128, 128)  # bm, bk, bn — MXU 128-aligned


def _kernel(x_mask_ref, w_mask_ref, x_ref, w_ref, *out_refs, k_index, skip, visits):
    o_ref = out_refs[0]
    visits_ref = out_refs[1] if visits else None
    k = pl.program_id(k_index)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if visits:
        first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (pl.program_id(2) == 0)

        @pl.when(first)
        def _init_visits():
            visits_ref[0] = 0

    live = jnp.logical_and(x_mask_ref[0, 0], w_mask_ref[0, 0])
    if not skip:
        # mask-only reference: a runtime-true predicate keeps the lowering
        # identical to the skipping path while executing every tile — exact
        # parity holds when dead tiles hold zeros (pruned operands)
        live = jnp.logical_or(live, jnp.logical_or(x_mask_ref[0, 0], ~x_mask_ref[0, 0]))

    @pl.when(live)
    def _mac():
        o_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)
        if visits:
            visits_ref[0] += 1


@functools.partial(jax.jit, static_argnames=("block", "dataflow", "skip", "with_visits", "interpret"))
def block_sparse_matmul(
    x: jax.Array,  # [M, K]
    w: jax.Array,  # [K, N]
    x_tile_mask: jax.Array | None = None,  # [M/bm, K/bk] bool (True = live)
    w_tile_mask: jax.Array | None = None,  # [K/bk, N/bn] bool
    *,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    dataflow: str = "ijk",  # "ijk" (k innermost, paper's [b,i,j,k]) | "kij"
    skip: bool = True,  # False = execute every tile (mask-only exact reference)
    with_visits: bool = False,  # also return the number of tile MACs issued
    interpret: bool | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bk, bn = block
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    if m % bm or k % bk or n % bn:
        raise ValueError(f"shapes {(m, k, n)} not divisible by block {(bm, bk, bn)}")
    gm, gk, gn = m // bm, k // bk, n // bn
    if x_tile_mask is None:
        x_tile_mask = jnp.ones((gm, gk), jnp.bool_)
    if w_tile_mask is None:
        w_tile_mask = jnp.ones((gk, gn), jnp.bool_)
    assert x_tile_mask.shape == (gm, gk) and w_tile_mask.shape == (gk, gn)

    if dataflow == "ijk":
        grid = (gm, gn, gk)
        ixw = lambda i, j, kk: (i, kk)
        www = lambda i, j, kk: (kk, j)
        out_map = lambda i, j, kk: (i, j)
        k_index = 2
    elif dataflow == "kij":
        grid = (gk, gm, gn)
        ixw = lambda kk, i, j: (i, kk)
        www = lambda kk, i, j: (kk, j)
        out_map = lambda kk, i, j: (i, j)
        k_index = 0
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    out_specs = pl.BlockSpec((bm, bn), out_map)
    out_shape = jax.ShapeDtypeStruct((m, n), jnp.float32)
    if with_visits:
        out_specs = (out_specs, pl.BlockSpec((1,), lambda *_: (0,)))
        out_shape = (out_shape, jax.ShapeDtypeStruct((1,), jnp.int32))
    return pl.pallas_call(
        functools.partial(_kernel, k_index=k_index, skip=skip, visits=with_visits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), ixw),
            pl.BlockSpec((1, 1), www),
            pl.BlockSpec((bm, bk), ixw),
            pl.BlockSpec((bk, bn), www),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(x_tile_mask, w_tile_mask, x, w)
