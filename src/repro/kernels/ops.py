"""Jitted public wrappers over the Pallas kernels with honest dispatch.

Backend selection is policy-driven: ``KernelPolicy.backend`` says which
datapath runs, and a Pallas request is *honored* — off-TPU it executes the
kernel body in interpret mode rather than silently falling back to the
reference path (the old ``attention`` bug: Pallas was never reachable on CPU,
and the reference branch crashed on the sparsity kwargs it claimed to accept).
Helpers that take no policy (``prune``, ``sparse_matmul``, ``wkv6``) keep the
historical backend-by-platform default.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.dynatran import block_mask
from repro.core.policy import KernelPolicy, on_tpu, resolve_policy

from . import ref
from .block_sparse_matmul import block_sparse_matmul
from .dynatran_prune import dynatran_prune
from .flash_attention import flash_attention
from .rwkv6_scan import wkv6_chunked

__all__ = [
    "dynatran_prune",
    "block_sparse_matmul",
    "flash_attention",
    "wkv6_chunked",
    "ref",
    "on_tpu",
    "attention",
    "ffn_block_sparse",
]


def _platform_policy() -> KernelPolicy:
    """The historical backend-by-platform default as a policy: fused kernels
    compiled on TPU, reference path elsewhere.  The ``interpret`` flag comes
    from the platform through ``KernelPolicy`` itself; kernel call sites
    route ``interpret=pol.interpret`` (reprolint PL404)."""
    return KernelPolicy(backend="pallas" if on_tpu() else "ref")


def prune(x, tau, *, policy=None, **kw):
    """DynaTran prune via the kernel on TPU, reference otherwise."""
    pol = policy if policy is not None else _platform_policy()
    if pol.use_pallas:
        return dynatran_prune(x, tau, interpret=pol.interpret, **kw)
    return ref.dynatran_prune_ref(x, tau)


def sparse_matmul(x, w, xm=None, wm=None, *, policy=None, **kw):
    pol = policy if policy is not None else _platform_policy()
    if pol.use_pallas:
        return block_sparse_matmul(x, w, xm, wm, interpret=pol.interpret, **kw)
    return ref.block_sparse_matmul_ref(x, w, xm, wm)


def attention(q, k, v, *, policy=None, sparsity=None, taus=None, **kw):
    """Flash attention dispatched by ``policy.backend`` — honestly.

    ``backend="pallas"`` runs the fused kernel (compiled on TPU, interpret
    mode elsewhere); ``backend="ref"`` runs the pure-jnp oracle.  With no
    policy and no legacy kwargs the platform default applies (Pallas on TPU).
    """
    if policy is None and sparsity is None and taus is None:
        policy = _platform_policy()
    pol = resolve_policy(policy, sparsity=sparsity, taus=taus)
    if pol.use_pallas:
        tau = pol.tau("attn_probs") if pol.wants("attn_probs") else 0.0
        return flash_attention(q, k, v, prune_tau=tau, interpret=pol.interpret, **kw)
    return ref.flash_attention_ref(q, k, v, policy=pol, **kw)


def ffn_block_sparse(hmid, w_down, policy):
    """Route pruned FFN activations through the tile-granular matmul.

    ``hmid [..., F]`` must already be DynaTran-pruned (dead elements exactly
    zero); a tile mask is derived from its zero pattern, the weights stay
    dense.  ``policy.skip`` selects skipping vs. the mask-only twin — both run
    the SAME tiled datapath, so their outputs are bitwise equal (a skipped
    tile's contribution is exactly 0.0).  Block edges clamp to gcd(shape,
    policy.block) so any model width tiles evenly.
    """
    x2 = hmid.reshape(-1, hmid.shape[-1])
    m, f = x2.shape
    d = w_down.shape[-1]
    bm, bk, bn = (math.gcd(m, policy.block), math.gcd(f, policy.block),
                  math.gcd(d, policy.block))
    xm = block_mask(x2 != 0, (bm, bk))
    w = w_down.astype(x2.dtype)
    sk = bool(policy.skip)
    if policy.use_pallas:
        out = block_sparse_matmul(
            x2, w, xm, None, block=(bm, bk, bn), skip=sk, interpret=policy.interpret
        )
    else:
        out = _ffn_block_sparse_ref(x2, w, xm, (bm, bk, bn), sk)
    return out.reshape(*hmid.shape[:-1], d).astype(hmid.dtype)


def _ffn_block_sparse_ref(x2, w, xm, block, skip):
    """CPU-honest tile skipping: scan over k tiles with a scalar ``lax.cond``
    per tile (XLA:CPU executes only the taken branch, so a dead activation
    feature-tile genuinely costs no MACs).  The mask-only twin uses a
    runtime-true predicate through the same cond, keeping the lowering — and
    therefore the bits — identical to the skipping path."""
    m, f = x2.shape
    d = w.shape[1]
    _bm, bk, _bn = block
    gk = f // bk
    xk = jnp.moveaxis(x2.reshape(m, gk, bk), 1, 0)  # [gk, M, bk]
    wk = w.reshape(gk, bk, d)
    col_live = jnp.any(xm, axis=0)  # [gk]: any row-block live for this k tile

    def body(acc, xs):
        xt, wt, live = xs
        if not skip:
            live = jnp.logical_or(live, jnp.logical_not(live))

        def mac(a):
            return a + jnp.dot(xt.astype(jnp.float32), wt.astype(jnp.float32))

        return jax.lax.cond(live, mac, lambda a: a, acc), None

    out, _ = jax.lax.scan(body, jnp.zeros((m, d), jnp.float32), (xk, wk, col_live))
    return out


def wkv6(r, k, v, w, u, *, policy=None, **kw):
    pol = policy if policy is not None else _platform_policy()
    if pol.use_pallas:
        return wkv6_chunked(r, k, v, w, u, interpret=pol.interpret, **kw)
    return ref.wkv6_ref(r, k, v, w, u)
