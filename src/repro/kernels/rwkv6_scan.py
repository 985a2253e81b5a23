"""Pallas kernel: chunked WKV-6 recurrence (RWKV "Finch" time-mix).

The sequential recurrence (models/rwkv6.wkv_sequential) is latency-bound on
TPU: S sequence steps each doing a rank-1 [N,N] update.  The chunked form
processes C tokens per grid step with dense [C,N]x[N,N] and [C,C] matmuls
(MXU work) and carries the [N,N] state in VMEM scratch across the sequential
chunk dimension:

    e_t   = prod_{u<t} w_u            (exclusive, within chunk, log-space)
    out_t = (r_t ⊙ e_t) · S_in + Σ_{s<t} [(r_t ⊙ e_t) · (k_s / e_{s+1})] v_s
            + (r_t ⊙ u ⊙ k_t) · v_t
    S_out = pw_C ⊙ (S_in + Σ_s (k_s / pw_s) ⊗ v_s),  pw inclusive prefix

Per-channel decays make the inner "attention" matrix A_ts = Σ_d r_td k_sd
exp(c_t,d - c_s+1,d); it is materialised per (t,s) pair in f32 with the
exponent difference computed before exponentiation (stable: t>s ⇒ diff ≤ 0).
Grid: (B*H, S/C) with the chunk axis sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, C, N):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)  # [C, N]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = jnp.log(jnp.maximum(w_ref[0].astype(jnp.float32), 1e-38))  # [C, N] (<= 0)
    u = u_ref[0].astype(jnp.float32)  # [1, N] broadcast row

    c_inc = jnp.cumsum(lw, axis=0)  # inclusive prefix log-decay
    c_exc = c_inc - lw  # exclusive

    s_in = s_scr[...]  # [N, N]
    # state contribution + intra-chunk strict lower triangle + diagonal bonus
    r_dec = r * jnp.exp(c_exc)  # r_t ⊙ e_t
    out = jnp.dot(r_dec, s_in, preferred_element_type=jnp.float32)  # [C, N]
    # A[t, s] = sum_d r_td k_sd exp(c_exc[t,d] - c_inc[s,d]) for s < t
    diff = c_exc[:, None, :] - c_inc[None, :, :]  # [C, C, N] (t, s, d)
    tri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) > jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    a = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(jnp.minimum(diff, 0.0)), axis=-1)
    a = jnp.where(tri, a, 0.0)
    a = a + jnp.sum(r * u * k, axis=-1)[:, None] * jnp.eye(C, dtype=jnp.float32)  # bonus diag
    out = out + jnp.dot(a, v, preferred_element_type=jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)

    # state update: S_out = diag(pw_C) S_in + Σ_s diag(pw_C / pw_s) k_s ⊗ v_s
    pw_c = jnp.exp(c_inc[-1])  # [N]
    k_scaled = k * jnp.exp(c_inc[-1][None, :] - c_inc)  # k_s * pw_C / pw_s  (≤ relative)
    s_scr[...] = pw_c[:, None] * s_in + jnp.dot(k_scaled.T, v, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_chunked(
    r: jax.Array,  # [B, S, H, N]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decays in (0, 1)
    u: jax.Array,  # [H, N]
    *,
    chunk: int = 32,
    interpret: bool | None = None,
) -> jax.Array:
    B, S, H, N = r.shape
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"S={S} not divisible by chunk={C}")
    perm = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    rr, kk, vv, ww = perm(r), perm(k), perm(v), perm(w)
    uu = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)
    grid = (B * H, S // C)
    out = pl.pallas_call(
        functools.partial(_kernel, C=C, N=N),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, C, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, C, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, C, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, N), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, N), r.dtype),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rr, kk, vv, ww, uu)
    return out.reshape(B, H, S, N).transpose(0, 2, 1, 3)
