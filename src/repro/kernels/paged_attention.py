"""Pallas kernels for the paged KV cache: gather, scatter, and a fused
paged decode-attention kernel that reads only live pages.

The serving engine keeps K/V in a global page pool ([num_pages, P, Hkv, D]
per layer cycle) with per-sequence page tables.  Three device paths:

* ``paged_gather``   — page table driven gather into a contiguous per-row
  cache view, via ``PrefetchScalarGridSpec``: the page table is a
  scalar-prefetch operand, so the *BlockSpec index map itself* resolves the
  page indirection and each grid cell DMAs exactly one page block.
* ``paged_scatter``  — one decode step's [B, Hkv, D] vectors written in
  place (``input_output_aliases``) at each row's (page, offset) by one DMA
  per row.
* ``paged_decode_attention`` — fused gather + online-softmax attention with
  a ``fori_loop`` bounded by the *live* page count per row, so HBM reads
  stop at ceil(len / P) pages instead of the max-length cache footprint
  (the dense decode path always streams max_len keys).  The pools stay in
  HBM; each live page is copied into VMEM with an async DMA, double-buffered
  so page p+1 streams in while page p is computed.

``interpret=None`` (the default everywhere) resolves from the platform
(``repro.core.policy.resolve_interpret``): compiled Mosaic on TPU, the
Pallas interpreter elsewhere.

Tensor parallelism: every kernel here is shard-local over the KV-head dim —
shapes are taken from the operands, and no op mixes heads — so the serving
engine calls them unchanged inside a ``shard_map`` over the mesh "model"
axis with pools of Hkv/n heads and q of H/n heads per shard (page tables
and lengths replicated; page ids are shard-invariant).  The grouped-query
ratio G = H // Hkv survives equal head splits, and per-head attention is
exact, so the sharded kernel output is the head-slice of the unsharded one
(asserted by tests/test_serve_tp.py on an emulated mesh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import resolve_interpret

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Gather: [num_pages, P, Hkv, D] + [B, maxp] -> [B, maxp * P, Hkv, D]
# ---------------------------------------------------------------------------


def _gather_kernel(pt_ref, pool_ref, out_ref):
    del pt_ref  # consumed by the index map
    out_ref[0, 0] = pool_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather(pool: jax.Array, page_table: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Page-table gather as a Pallas kernel.

    Grid is (B, maxp); the pool BlockSpec's index map reads the prefetched
    page table, so grid cell (b, p) DMAs pool page ``page_table[b, p]``
    straight into its output block — no materialised index arrays.
    """
    b, maxp = page_table.shape
    n_pages, p, hkv, d = pool.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, p, hkv, d), lambda i, j, pt: (pt[i, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, p, hkv, d), lambda i, j, pt: (i, j, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, maxp, p, hkv, d), pool.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table, pool)
    return out.reshape(b, maxp * p, hkv, d)


# ---------------------------------------------------------------------------
# Scatter: write one decode step's K or V vectors into the pool in place.
# ---------------------------------------------------------------------------


def _scatter_kernel(pt_ref, len_ref, new_ref, pool_ref, out_ref, sem):
    del pool_ref  # aliased with out_ref
    b = pl.program_id(0)
    page_size = out_ref.shape[1]
    length = len_ref[b]
    idx = length // page_size

    # a row whose position falls past its table is dropped, exactly as
    # kvcache.scatter_token routes it to an out-of-bounds sink
    @pl.when(idx < pt_ref.shape[1])
    def _():
        page = pt_ref[b, jnp.minimum(idx, pt_ref.shape[1] - 1)]
        copy = pltpu.make_async_copy(new_ref.at[0], out_ref.at[page, length % page_size], sem)
        copy.start()
        copy.wait()


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def paged_scatter(
    pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    new: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Insert ``new`` [B, Hkv, D] at each row's current write position.

    The pool is donated and aliased to the output, so the update is in
    place — the kernel DMAs exactly B (page, offset) cells.
    """
    b, hkv, d = new.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, d), lambda i, pt, ln: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        interpret=resolve_interpret(interpret),
    )(page_table, lengths, new.astype(pool.dtype), pool)


# ---------------------------------------------------------------------------
# Fused paged decode attention: online softmax over live pages only.
# ---------------------------------------------------------------------------


def _attn_kernel(pt_ref, len_ref, q_ref, *refs, page_size, logit_cap, window, quant, occupancy, skip, visits):
    """Online-softmax decode attention over live pages.

    Layout: one grid cell per row; q is [Hkv, G, D].  A page of K (and V) is
    copied into VMEM as [P, Hkv, D], widened to f32 and turned head-major
    ([Hkv, P, D]), so every KV head runs its own online softmax over its own
    [P, D] block.  Nothing mixes heads, so a head's result does not depend on
    how many heads the kernel holds (a TP shard's output is bitwise the head
    slice of the unsharded one).

    ``quant``: k/v pools are int8 with parallel bf16 scale pools
    ([num_pages, P, Hkv]); dequantisation is fused into the page load.
    ``window``: ring table — a table of C = maxp * P logical ring slots
    holding the trailing ``window`` positions; page slot offsets are mapped
    back to absolute positions and masked to the window.
    ``occupancy``: a DynaTran "kv" occupancy pool [num_pages, P] rides along
    (as int32); dead positions mask out, and with ``skip`` a page whose every
    in-range position is dead is jumped over — no page DMA, no MACs.
    Skipping is EXACT, not approximate: an all-dead page contributes
    probability 0.0 everywhere, and the query's own position is always kept
    live so at least one live position exists.  Both modes route through the
    same ``lax.cond``, so skip=True and skip=False are bitwise equal.
    ``visits``: emit a per-row int32 count of pages actually processed — the
    bench's tile-traffic meter.
    """
    k_hbm, v_hbm = refs[0], refs[1]
    ks_ref, vs_ref = (refs[2], refs[3]) if quant else (None, None)
    n_in = 2 + (2 if quant else 0) + (1 if occupancy else 0)
    occ_ref = refs[n_in - 1] if occupancy else None
    out_ref = refs[n_in]
    visits_ref = refs[n_in + 1] if visits else None
    kbuf, vbuf, sems = refs[-3:]

    b = pl.program_id(0)
    hkv, g, d = q_ref.shape[1:]
    length = len_ref[b]  # tokens in the cache, INCLUDING the current one
    maxp = pt_ref.shape[1]
    n_live = jnp.minimum((length + page_size - 1) // page_size, maxp)
    slot_t = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)  # [1, P]

    def dmas(p, slot):
        page = pt_ref[b, p]
        return [pltpu.make_async_copy(src.at[page], buf.at[slot], sems.at[i, slot])
                for i, (src, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    def fetch(p, slot):
        for copy in dmas(p, slot):
            copy.start()

    def wait(p, slot):
        for copy in dmas(p, slot):
            copy.wait()

    def page_row(ref, p):
        """Page p's row of a per-page side table held in VMEM."""
        return ref[pl.ds(pt_ref[b, p], 1), :]

    def page_matrix(buf, scale_ref, p, slot):
        x = buf[slot].astype(jnp.float32).reshape(page_size * hkv, d)  # [C, D]
        if scale_ref is not None:
            # the [1, C] scale row as a [C, 1] column, via a 2-D transpose
            row = jnp.broadcast_to(page_row(scale_ref, p), (8, page_size * hkv))
            col = jnp.transpose(row)[:, :1]
            # round through bf16 like kvcache.dequantize_kv
            x = (x * col).astype(jnp.bfloat16).astype(jnp.float32)
        return x

    def positions(p):
        """Absolute position held by each slot of page p, and whether it is
        in range ([1, P] each)."""
        if window is None:
            pos = p * page_size + slot_t
            return pos, pos < length
        # ring slot `off` holds the largest absolute position a <= L with
        # a % C == off (L = length - 1, the query's position); shared window
        # convention: valid iff a > L - window and a >= 0
        capacity = maxp * page_size
        r = jax.lax.rem(length - 1 - p * page_size + capacity, capacity)  # in [0, C)
        x = r - slot_t
        pos = (length - 1) - jnp.where(x < 0, x + capacity, x)
        return pos, (pos >= 0) & (pos > length - 1 - window)

    def head_major(buf, scale_ref, p, slot):
        """The page as [Hkv, P, D] f32: one [P, D] block per KV head."""
        x = page_matrix(buf, scale_ref, p, slot).reshape(page_size, hkv, d)
        return jnp.swapaxes(x, 0, 1)

    def compute(p, slot, valid, carry):
        heads, n_vis = carry
        ks = head_major(kbuf, ks_ref, p, slot)
        vs = head_major(vbuf, vs_ref, p, slot)
        out = []
        for n, (m, lsum, acc) in enumerate(heads):
            k, v = ks[n], vs[n]
            q = q_ref[0, n].astype(jnp.float32)  # [G, D], pre-scaled
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            if logit_cap is not None and logit_cap > 0:
                s = logit_cap * jnp.tanh(s / logit_cap)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            probs = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            out.append((m_new, lsum * corr + probs.sum(-1, keepdims=True),
                        acc * corr + jnp.dot(probs, v, preferred_element_type=jnp.float32)))
        return tuple(out), n_vis + 1

    if occupancy:
        def body(p, carry):
            pos, valid = positions(p)
            # the query's own position is always live: guarantees >= 1 live
            # position per row, which is what makes page-skipping exact
            valid = valid & ((page_row(occ_ref, p) != 0) | (pos == length - 1))
            page_live = jnp.any(valid)
            if not skip:
                page_live = jnp.logical_or(page_live, length >= 0)

            def visit(c):
                fetch(p, 0)
                wait(p, 0)
                return compute(p, 0, valid, c)

            return jax.lax.cond(page_live, visit, lambda c: c, carry)
    else:
        def body(p, carry):
            slot = p % 2

            @pl.when(p + 1 < n_live)
            def _():
                fetch(p + 1, 1 - slot)

            wait(p, slot)
            return compute(p, slot, positions(p)[1], carry)

        @pl.when(n_live > 0)
        def _():
            fetch(0, 0)

    head0 = (jnp.full((g, 1), NEG_INF, jnp.float32), jnp.zeros((g, 1), jnp.float32),
             jnp.zeros((g, d), jnp.float32))
    heads, n_vis = jax.lax.fori_loop(0, n_live, body, ((head0,) * hkv, jnp.int32(0)))
    for n, (_, lsum, acc) in enumerate(heads):
        out_ref[0, n] = (acc / jnp.maximum(lsum, 1e-30)).astype(out_ref.dtype)
    if visits:
        visits_ref[b] = n_vis


@functools.partial(
    jax.jit, static_argnames=("window", "logit_cap", "scale", "skip", "with_visits", "interpret")
)
def paged_decode_attention(
    q: jax.Array,  # [B, 1, H, D]
    k_pool: jax.Array,  # [num_pages, P, Hkv, D] (bf16/f32, or int8 with scales)
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, maxp] int32
    lengths: jax.Array,  # [B] int32 — valid tokens in the cache (incl. the current one)
    *,
    k_scale: jax.Array | None = None,  # [num_pages, P, Hkv] bf16 — int8 absmax scales
    v_scale: jax.Array | None = None,
    occupancy: jax.Array | None = None,  # [num_pages, P] bool — DynaTran "kv" liveness
    window: int | None = None,  # set for ring tables: mask to the sliding window
    logit_cap: float | None = None,
    scale: float | None = None,
    skip: bool = True,  # skip all-dead pages (False = mask-only exact reference)
    with_visits: bool = False,  # also return per-row processed-page counts
    interpret: bool | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """One query per row against its paged cache; reads ceil(len/P) pages
    (clamped to the table width for ring tables).

    Equivalent to ``attention.decode_attention`` on the gathered (and
    dequantised) cache view up to online-softmax float reassociation
    (~1e-6 relative).  int8 pools pass ``k_scale``/``v_scale``; ring tables
    pass ``window`` and a table whose C = maxp * P ring slots hold the
    trailing window (position t at slot t % C).

    ``occupancy`` (from the "kv-occupancy" side array of the page pools)
    masks DynaTran-dead positions; ``skip=True`` additionally jumps all-dead
    pages — with ``with_visits=True`` the second return value counts pages
    actually processed per row, which the bench asserts falls as rho rises.
    ``skip=True`` and ``skip=False`` are exactly equal (see ``_attn_kernel``).

    Under tensor parallelism, call with the shard-local pools and the
    matching q head block (H/n query heads against Hkv/n pool heads): all
    shapes derive from the operands and no reduction crosses KV heads, so
    the kernel is oblivious to running inside a ``shard_map``.  Occupancy is
    per-position, so the SAME (replicated) occupancy array goes to every
    shard.
    """
    b, _, h, d = q.shape
    _, page_size, hkv, _ = k_pool.shape
    g = h // hkv
    quant = k_scale is not None
    scale = scale if scale is not None else d**-0.5
    qg = (q[:, 0].astype(jnp.float32) * scale).reshape(b, hkv, g, d)  # head h = n * G + g
    # per-page side tables held whole in VMEM, one row per page: int8 scales
    # as [num_pages, P * Hkv] (the page matrix's row order), occupancy as
    # [num_pages, P]
    tables = ()
    if quant:
        tables += tuple(x.reshape(x.shape[0], -1).astype(jnp.float32) for x in (k_scale, v_scale))
    if occupancy is not None:
        tables += (occupancy.astype(jnp.int32),)
    operands = (page_table, lengths, qg, k_pool, v_pool) + tables
    in_specs = (
        [pl.BlockSpec((1, hkv, g, d), lambda i, pt, ln: (i, 0, 0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        + [pl.BlockSpec(t.shape, lambda i, pt, ln: (0, 0)) for t in tables]
    )
    # two VMEM page buffers per pool (double buffering) + their semaphores
    scratch = [pltpu.VMEM((2,) + k_pool.shape[1:], k_pool.dtype),
               pltpu.VMEM((2,) + v_pool.shape[1:], v_pool.dtype),
               pltpu.SemaphoreType.DMA((2, 2))]
    out_specs = pl.BlockSpec((1, hkv, g, d), lambda i, pt, ln: (i, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32)
    if with_visits:
        out_specs = (out_specs, pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape = (out_shape, jax.ShapeDtypeStruct((b,), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _attn_kernel, page_size=page_size, logit_cap=logit_cap, window=window, quant=quant,
        occupancy=occupancy is not None, skip=skip, visits=with_visits,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(*operands)
    if with_visits:
        out, visits = out
        return out.reshape(b, 1, h, d).astype(q.dtype), visits
    return out.reshape(b, 1, h, d).astype(q.dtype)
