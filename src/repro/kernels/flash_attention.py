"""Pallas kernel: flash attention (online softmax) with causal masking,
sliding-window banding and gemma-2 logit soft-capping — the specialised
"softmax module" of AccelTran, TPU-style: instead of a dedicated exp/sum
datapath next to the MAC lanes, the softmax is fused *into* the matmul
pipeline so probabilities never round-trip HBM.

Grid: (batch*q_heads, Sq/bq, Skv/bk), kv innermost (sequential); running
(m, l, acc) carried in VMEM scratch across the kv dimension.  Causal and
window constraints skip whole kv blocks via `@pl.when` — the same
"skip ineffectual tiles" motif as the block-sparse matmul.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, tau_ref, o_ref, m_scr, l_scr, acc_scr, *, bq, bk, skv, causal, window, cap, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # block-level skip: fully-masked kv blocks do no work at all
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < skv
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # DynaTran site "attn_probs", fused: threshold block-local normalised
        # probabilities (the ASIC's one-cycle comparator bank sits directly
        # in the softmax datapath).  tau <= 0 -> dense.
        tau = tau_ref[0]
        p_norm = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-9)
        p = jnp.where(jnp.logical_or(tau <= 0.0, p_norm >= tau), p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "logit_cap", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,  # [B, S, H, D] (MHA; GQA callers repeat logically upstream)
    k: jax.Array,  # [B, Skv, H, D]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    logit_cap: float | None = None,
    prune_tau: jax.Array | float = 0.0,  # DynaTran attn-prob threshold (runtime input, no recompile)
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, skv, hk, _ = k.shape
    assert h == hk, "kernel is MHA-shaped; expand GQA groups before the call"
    bq, bk = min(block_q, sq), min(block_k, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"seq lens {(sq, skv)} not divisible by blocks {(bq, bk)}")
    scale = 1.0 / math.sqrt(d)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    tau_arr = jnp.asarray(prune_tau, jnp.float32).reshape(1)
    grid = (b * h, sq // bq, skv // bk)
    out = pl.pallas_call(
        functools.partial(
            _kernel, bq=bq, bk=bk, skv=skv, causal=causal, window=window, cap=logit_cap, scale=scale
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1,), lambda bh, qi, ki: (0,)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qr, kr, vr, tau_arr)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
