"""Generic decoder-only transformer LM covering the dense / MoE / VLM /
hybrid (attention ⊕ SSM) families of the assigned pool.

Key structural choices (production patterns):

* **Pattern-cycle layer scan.** Layer parameters are stacked with leading
  axis ``n_cycles = layers / len(attention_pattern)`` and scanned with
  ``lax.scan``; the scan body statically applies one block per pattern entry.
  This keeps HLO size depth-independent *and* supports heterogeneous layer
  stacks (gemma-2's local/global alternation) with static attention code per
  position — the banded sliding-window path keeps its O(S·W) cost.
* **GQA without KV repetition**, chunked flash attention for "full" layers,
  block-banded attention for "sliding" layers.
* **DynaTran sites** threaded through every block (ffn_act, attn_probs,
  attn_out, block_out) — identity when mode=="none".
* Remat policy on the scan body (``cfg.remat``).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.dynatran import SparsityConfig, site_prune
from repro.core.policy import KernelPolicy, resolve_policy
from repro.launch.sharding import constrain
from . import attention as attn
from .kvcache import (
    DecodeState,
    PagedKV,
    PagedLayout,
    StateBundle,
    StateComponent,
    copy_pool_pages,
    entry_copy_pages,
    entry_extract_pages,
    entry_gather,
    entry_gather_ring,
    entry_scatter_chunk,
    entry_scatter_token,
    entry_insert_pages,
    init_occupancy,
    init_paged_pools,
    occupancy_bit,
    quantize_kv,
    dequantize_kv,
    scatter_chunk,
    scatter_chunk_ring,
    scatter_token,
    scatter_token_ring,
)
from .layers import ACTIVATIONS, apply_mrope, apply_rope, dense_init, embed_init, make_norm, rms_norm, softcap
from .moe import moe_ffn, moe_init
from .ssm import ssm_init, ssm_mix, ssm_state_init

Array = jax.Array


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(key: Array, cfg: ModelConfig, pattern: str, dtype) -> dict:
    D, F, H, Hkv, hd = cfg.d_model, cfg.d_ff, cfg.heads, cfg.kv_heads, cfg.hd
    norm_init, _ = make_norm(cfg.norm)
    ks = iter(jax.random.split(key, 12))
    p: dict[str, Any] = {
        "ln1": norm_init(D),
        "wq": dense_init(next(ks), (D, H, hd), dtype=dtype),
        "wk": dense_init(next(ks), (D, Hkv, hd), dtype=dtype),
        "wv": dense_init(next(ks), (D, Hkv, hd), dtype=dtype),
        "wo": dense_init(next(ks), (H, hd, D), dtype=dtype),
        "ln2": norm_init(D),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.zeros((hd,), jnp.float32)}
    if cfg.post_norms:
        p["post_attn_norm"] = norm_init(D)
        p["post_mlp_norm"] = norm_init(D)
    if cfg.n_experts:
        p["moe"] = moe_init(next(ks), D, cfg.n_experts, cfg.moe_d_ff or F, cfg.glu, dtype=dtype)
    else:
        p["mlp"] = {
            "w_up": dense_init(next(ks), (D, F), dtype=dtype),
            "w_down": dense_init(next(ks), (F, D), dtype=dtype),
        }
        if cfg.glu:
            p["mlp"]["w_gate"] = dense_init(next(ks), (D, F), dtype=dtype)
    if cfg.ssm_state:
        p["ssm"] = ssm_init(next(ks), D, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, dtype=dtype)
        p["ssm_ln"] = norm_init(D)
    return p


def init_params(key: Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    kemb, khead, kblocks = jax.random.split(key, 3)
    params: dict[str, Any] = {"embed": embed_init(kemb, cfg.vocab_padded, cfg.d_model, dtype=dtype)}
    norm_init, _ = make_norm(cfg.norm)
    params["final_norm"] = norm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(khead, (cfg.d_model, cfg.vocab_padded), dtype=dtype)
    if cfg.pos_kind == "learned":
        params["pos_embed"] = embed_init(khead, cfg.max_positions, cfg.d_model, dtype=dtype)

    def one_cycle(ck):
        cks = jax.random.split(ck, cfg.pattern_len)
        return {str(i): _block_init(cks[i], cfg, pat, dtype) for i, pat in enumerate(cfg.attention_pattern)}

    # stacked cycles (leading axis n_cycles on every block leaf), built in one
    # jitted vmap: the device never holds per-cycle arrays next to the stack
    cycle_keys = jax.random.split(kblocks, cfg.n_cycles)
    params["blocks"] = jax.jit(jax.vmap(one_cycle))(cycle_keys)
    return params


def abstract_params(cfg: ModelConfig) -> Any:
    """Parameter pytree of ShapeDtypeStructs (no allocation) for the dry-run."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _qkv(p: dict, cfg: ModelConfig, h: Array, positions: Array, positions_3d: Array | None):
    _, norm = make_norm(cfg.norm)
    x = norm(p["ln1"], h)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q)
        k = rms_norm(p["k_norm"], k)
    if cfg.pos_kind == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_kind == "mrope":
        assert positions_3d is not None
        q = apply_mrope(q, positions_3d, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions_3d, cfg.mrope_sections, cfg.rope_theta)
    return x, q, k, v


def _mlp(p: dict, cfg: ModelConfig, x: Array, pol: KernelPolicy) -> tuple[Array, dict]:
    if cfg.n_experts:
        return moe_ffn(
            p["moe"],
            x,
            n_experts=cfg.n_experts,
            top_k=cfg.experts_per_token,
            act=cfg.act,
            glu=cfg.glu,
            capacity_factor=cfg.capacity_factor,
            policy=pol,
        )
    act = ACTIVATIONS[cfg.act]
    up = x @ p["mlp"]["w_up"].astype(x.dtype)
    hmid = act(x @ p["mlp"]["w_gate"].astype(x.dtype)) * up if cfg.glu else act(up)
    if pol.wants("ffn_act"):
        hmid = pol.prune(hmid, "ffn_act")
        if pol.tiled:
            # tile-granular down-projection: dead activation tiles skip the
            # MAC outright (ops.ffn_block_sparse; skip=False is the bitwise
            # mask-only twin).  Legacy policies (skip=None) keep the dense
            # matmul below — old numerics, bit for bit.
            from repro.kernels.ops import ffn_block_sparse

            return ffn_block_sparse(hmid, p["mlp"]["w_down"], pol), {}
    return hmid @ p["mlp"]["w_down"].astype(x.dtype), {}


def block_apply(
    p: dict,
    cfg: ModelConfig,
    pattern: str,
    h: Array,
    positions: Array,
    positions_3d: Array | None,
    pol: KernelPolicy,
) -> tuple[Array, dict]:
    """One transformer block, prefill/train mode."""
    _, norm = make_norm(cfg.norm)
    x, q, k, v = _qkv(p, cfg, h, positions, positions_3d)
    q, k, v = (constrain(t, "attn_qkv") for t in (q, k, v))
    win = cfg.window if (pattern == "sliding" and cfg.window) else None
    ao = attn.chunked_attention(
        q, k, v, causal=True, window=win, logit_cap=cfg.attn_logit_cap,
        chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k, policy=pol
    )
    ao = pol.prune(ao, "attn_out")
    attn_out = jnp.einsum("bshk,hkd->bsd", ao, p["wo"].astype(ao.dtype))
    if cfg.ssm_state:  # hymba: SSM path in parallel with attention
        ssm_out, _ = ssm_mix(p["ssm"], norm(p["ssm_ln"], h))
        attn_out = (attn_out + ssm_out) * 0.5
    if cfg.post_norms:
        attn_out = norm(p["post_attn_norm"], attn_out)
    h = h + attn_out
    mlp_out, metrics = _mlp(p, cfg, norm(p["ln2"], h), pol)
    if cfg.post_norms:
        mlp_out = norm(p["post_mlp_norm"], mlp_out)
    h = h + mlp_out
    h = pol.prune(h, "block_out")
    return h, metrics


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: Array,  # [B, S]
    *,
    embeds: Array | None = None,  # [vlm]: precomputed patch/text embeddings
    positions_3d: Array | None = None,
    policy: KernelPolicy | None = None,
    taus=None,  # deprecated: pass policy=
    last_only: bool = False,
) -> tuple[Array, dict]:
    """Returns (logits [B,S,V], metrics).  ``last_only`` slices the final
    hidden state to the last position BEFORE the LM head — serving prefill
    only needs next-token logits, and the full-sequence head matmul is the
    single largest FLOP term of the prefill step (2*B*S*D*V)."""
    pol = resolve_policy(policy, taus=taus, default_sparsity=cfg.sparsity)
    B, S = tokens.shape
    h = params["embed"][tokens] if embeds is None else embeds.astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        h = h * jnp.sqrt(float(cfg.d_model)).astype(h.dtype)
    if cfg.pos_kind == "learned":
        h = h + params["pos_embed"][jnp.arange(S) % params["pos_embed"].shape[0]]
    positions = jnp.arange(S)

    aux = {"moe_aux_loss": jnp.zeros((), jnp.float32)}

    h = constrain(h, "residual")

    def cycle_body(carry, cycle_params):
        hh, aux_acc = carry
        for i, pat in enumerate(cfg.attention_pattern):
            hh, m = block_apply(cycle_params[str(i)], cfg, pat, hh, positions, positions_3d, pol)
            hh = constrain(hh, "residual")
            if "moe_aux_loss" in m:
                aux_acc = {"moe_aux_loss": aux_acc["moe_aux_loss"] + m["moe_aux_loss"]}
        return (hh, aux_acc), ()

    body = cycle_body
    if cfg.remat != "none":
        ckpt_policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if cfg.remat == "save_dots"
            else jax.checkpoint_policies.nothing_saveable
        )
        body = jax.checkpoint(cycle_body, policy=ckpt_policy, prevent_cse=True)

    (h, aux), _ = jax.lax.scan(body, (h, aux), params["blocks"])
    _, norm = make_norm(cfg.norm)
    if last_only:
        h = h[:, -1:]
    h = norm(params["final_norm"], h)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head.astype(h.dtype)
    logits = constrain(softcap(logits.astype(jnp.float32), cfg.final_logit_cap), "logits")
    return logits, aux


# ---------------------------------------------------------------------------
# decode (serve_step): one token against the cache
# ---------------------------------------------------------------------------


def _quant_update(cache: dict, new: Array, rows: Array, pos: Array) -> dict:
    """Insert one step's [B, Hkv, hd] vectors with per-(row, head) absmax
    int8 quantisation (the same ops the paged int8 pools use, so the two
    caches hold identical bits)."""
    q, scale = quantize_kv(new)
    return {
        "q": cache["q"].at[rows, pos].set(q),
        "scale": cache["scale"].at[rows, pos].set(scale),
    }


def _dequant(cache: dict) -> Array:
    return dequantize_kv(cache["q"], cache["scale"])


def _cache_len_for(cfg: ModelConfig, pattern: str, max_len: int) -> int:
    if pattern == "sliding" and cfg.window:
        return min(cfg.window, max_len)
    return max_len


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> DecodeState:
    k = {}
    v = {}
    quant = cfg.kv_cache_dtype == "int8"
    for i, pat in enumerate(cfg.attention_pattern):
        T = _cache_len_for(cfg, pat, max_len)
        shape = (cfg.n_cycles, batch, T, cfg.kv_heads, cfg.hd)
        if quant:
            # int8 cache + per-(position, head) absmax scale: halves the
            # decode step's dominant HBM term (the cache read)
            k[str(i)] = {"q": jnp.zeros(shape, jnp.int8), "scale": jnp.zeros(shape[:-1], jnp.bfloat16)}
            v[str(i)] = {"q": jnp.zeros(shape, jnp.int8), "scale": jnp.zeros(shape[:-1], jnp.bfloat16)}
        else:
            k[str(i)] = jnp.zeros(shape, dtype)
            v[str(i)] = jnp.zeros(shape, dtype)
    ssm = None
    if cfg.ssm_state:
        ssm = {
            str(i): jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (cfg.n_cycles,) + x.shape),
                ssm_state_init(batch, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, dtype),
            )
            for i in range(cfg.pattern_len)
        }
    return DecodeState(k=k, v=v, ssm=ssm, length=jnp.zeros((batch,), jnp.int32))


def decode_step(
    params: dict,
    cfg: ModelConfig,
    state: DecodeState,
    tokens: Array,  # [B, 1]
    *,
    positions_3d: Array | None = None,
    policy: KernelPolicy | None = None,
    taus=None,  # deprecated: pass policy=
) -> tuple[Array, DecodeState]:
    """One serve step: logits for the next token + updated caches."""
    pol = resolve_policy(policy, taus=taus, default_sparsity=cfg.sparsity)
    B = tokens.shape[0]
    h = params["embed"][tokens]
    if cfg.embed_scale:
        h = h * jnp.sqrt(float(cfg.d_model)).astype(h.dtype)
    length = state.length  # [B]
    if cfg.pos_kind == "learned":
        h = h + params["pos_embed"][length[:, None] % params["pos_embed"].shape[0]]
    positions = length[:, None]  # [B,1]
    _, norm = make_norm(cfg.norm)

    def cycle_body(carry, xs):
        hh = carry
        cycle_params, kc, vc, ssmc = xs
        new_k, new_v, new_ssm = {}, {}, {}
        for i, pat in enumerate(cfg.attention_pattern):
            p = cycle_params[str(i)]
            x, q, k1, v1 = _qkv(p, cfg, hh, positions, positions_3d)
            quant = isinstance(kc[str(i)], dict)
            T = (kc[str(i)]["q"] if quant else kc[str(i)]).shape[1]
            ring = pat == "sliding" and cfg.window and T == cfg.window
            pos = length % T if ring else jnp.minimum(length, T - 1)
            rows = jnp.arange(B)
            if quant:
                kcache = _quant_update(kc[str(i)], k1[:, 0], rows, pos)
                vcache = _quant_update(vc[str(i)], v1[:, 0], rows, pos)
                k_read = _dequant(kcache)
                v_read = _dequant(vcache)
            else:
                kcache = kc[str(i)].at[rows, pos].set(k1[:, 0].astype(kc[str(i)].dtype))
                vcache = vc[str(i)].at[rows, pos].set(v1[:, 0].astype(vc[str(i)].dtype))
                k_read, v_read = kcache, vcache
            eff_len = jnp.minimum(length + 1, T)
            ao = attn.decode_attention(
                q, k_read, v_read, eff_len, window=None, logit_cap=cfg.attn_logit_cap
            )
            ao = pol.prune(ao, "attn_out")
            attn_out = jnp.einsum("bshk,hkd->bsd", ao, p["wo"].astype(ao.dtype))
            if cfg.ssm_state:
                ssm_out, s_new = ssm_mix(p["ssm"], norm(p["ssm_ln"], hh), state=ssmc[str(i)])
                attn_out = (attn_out + ssm_out) * 0.5
                new_ssm[str(i)] = s_new
            if cfg.post_norms:
                attn_out = norm(p["post_attn_norm"], attn_out)
            hh = hh + attn_out
            mlp_out, _ = _mlp(p, cfg, norm(p["ln2"], hh), pol)
            if cfg.post_norms:
                mlp_out = norm(p["post_mlp_norm"], mlp_out)
            hh = hh + mlp_out
            new_k[str(i)], new_v[str(i)] = kcache, vcache
        return hh, (new_k, new_v, new_ssm if cfg.ssm_state else None)

    xs = (params["blocks"], state.k, state.v, state.ssm if cfg.ssm_state else jnp.zeros((cfg.n_cycles,)))
    h, (ks, vs, ssms) = jax.lax.scan(cycle_body, h, xs)
    h = norm(params["final_norm"], h)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head.astype(h.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_cap)
    logits = constrain(logits[:, 0], "logits_2d")
    new_state = DecodeState(k=ks, v=vs, ssm=ssms if cfg.ssm_state else None, length=length + 1)
    return logits, new_state


# ---------------------------------------------------------------------------
# Paged decode/prefill: the continuous-batching serve path.  K/V live in
# per-pattern-slot page pools shared across sequences; per-row page tables
# (one per page KIND — append-only "full" tables, fixed-budget "ring"
# tables for sliding-window layers) resolve the indirection.  The jnp read
# path is bitwise-identical to ``decode_step`` on a dense cache for every
# supported cache flavour — full, ring, and int8-quantised — because the
# gather reproduces the dense cache's values in the dense cache's order and
# masked scores are exactly NEG_INF either way.  The Pallas path
# (``use_pallas=True``) fuses gather + dequant + attention and reads only
# live pages, at online-softmax accuracy.  Hybrid (attention ⊕ SSM) models
# carry their O(1)-per-sequence recurrent state densely per batch row
# alongside the pools.
# ---------------------------------------------------------------------------


def check_paged_support(cfg: ModelConfig) -> None:
    """Serve support is a registry property: does the family declare a
    decode-state bundle?  (Kept under its historical name; delegates to the
    zoo-level check so every caller sees the same registry.)"""
    from repro.models import zoo

    zoo.check_serve_support(cfg)


def serve_state_bundle(cfg: ModelConfig, layout: PagedLayout | None = None) -> StateBundle:
    """The transformer families' declared decode state: one paged component
    per page kind in the layout (int8 pools are their own registered kind),
    plus slot-dense SSM side-state for hybrid models.  With ``layout=None``
    (support checks, before a serving shape exists) kinds are derived from
    the attention pattern alone."""
    if cfg.family == "vlm":
        raise NotImplementedError(
            "serve: vlm decode needs per-step patch embeds / 3-D M-RoPE "
            "positions, which the paged step does not thread yet"
        )
    if layout is not None:
        kinds = layout.kinds
    else:
        pattern_kinds = {
            "ring" if (p == "sliding" and cfg.window) else "full" for p in cfg.attention_pattern
        }
        kinds = tuple(k for k in ("full", "ring") if k in pattern_kinds)
    quant = cfg.kv_cache_dtype == "int8"
    comps = []
    for kind in kinds:
        if kind == "ring":
            comps.append(StateComponent("kv-ring", "paged-ring"))
        else:
            comps.append(StateComponent("kv", "paged-int8" if quant else "paged-full"))
    if cfg.ssm_state:
        comps.append(StateComponent("ssm", "slot-ssm"))
    return StateBundle(tuple(comps))


# --- tensor parallelism over the KV-head dim --------------------------------
#
# The paged steps take ``tp=(axis_name, n_shards)`` when traced INSIDE a
# ``shard_map`` over a mesh axis (see ``make_tp_paged_fns``).  The sharded
# quantities are exactly the attention inner loop: each shard holds the page
# pools for Hkv/n KV heads (page ids are shard-invariant), scatters its own
# head-slice of the new K/V, gathers/attends over its pool shard, and the
# per-head attention outputs are reassembled with an ``all_gather`` (pure
# data movement).  Everything else — projections, norms, MLP, SSM side-state,
# the LM head — is computed replicated, identically on every shard.
#
# Because attention is computed per (kv-head, group) slice with elementwise/
# per-head ops, and the all_gather concatenates exact per-head results, the
# TP step is BITWISE-identical to the single-device step: slicing the head
# axis commutes with every op in the attention path.


def _tp_slice_heads(tp: tuple[str, int] | None, q: Array, k1: Array, v1: Array):
    """Slice q/k/v [B, S, H(kv), D] to this shard's contiguous head block.
    GQA grouping is contiguous (head h belongs to kv head h // G), so equal
    H and Hkv splits keep every query head with its KV head."""
    if tp is None:
        return q, k1, v1
    ax, n = tp
    idx = jax.lax.axis_index(ax)
    hq, hkv = q.shape[2] // n, k1.shape[2] // n
    q = jax.lax.dynamic_slice_in_dim(q, idx * hq, hq, axis=2)
    k1 = jax.lax.dynamic_slice_in_dim(k1, idx * hkv, hkv, axis=2)
    v1 = jax.lax.dynamic_slice_in_dim(v1, idx * hkv, hkv, axis=2)
    return q, k1, v1


def _tp_gather_heads(tp: tuple[str, int] | None, ao: Array) -> Array:
    """Reassemble the full [B, S, H, D] attention output from per-shard head
    blocks (concatenation only — no arithmetic, so exactness is preserved)."""
    if tp is None:
        return ao
    return jax.lax.all_gather(ao, tp[0], axis=2, tiled=True)


def paged_layout(cfg: ModelConfig, max_len: int, page_size: int, lookahead: int = 1) -> PagedLayout:
    """Static page-kind layout for this config at a serving shape.
    ``lookahead`` is the engine's multi-step decode window (ring budgets
    must cover it — see PagedLayout)."""
    check_paged_support(cfg)
    return PagedLayout.for_config(cfg, max_len, page_size, lookahead)


# serve-protocol aliases (the engine drives every family through the same
# names; see zoo.serve_module)
serve_layout = paged_layout


def init_paged_state(
    cfg: ModelConfig, layout: PagedLayout, num_pages: dict[str, int] | int, dtype=jnp.bfloat16
) -> PagedKV:
    check_paged_support(cfg)
    return init_paged_pools(
        layout, cfg.n_cycles, num_pages, cfg.kv_heads, cfg.hd, dtype,
        quant=cfg.kv_cache_dtype == "int8",
    )


def init_paged_ssm(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    """Recurrent side-state for hybrid models, stacked like the dense decode
    state: pattern slot -> leaves [n_cycles, B, ...].  None when the model
    has no SSM heads."""
    if not cfg.ssm_state:
        return None
    return {
        str(i): jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_cycles,) + x.shape),
            ssm_state_init(batch, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, dtype),
        )
        for i in range(cfg.pattern_len)
    }


# the transformer families' slot-dense state is the hybrid SSM side-state
# (the "slot-ssm" kind of the bundle); None for pure-attention models
init_slot_state = init_paged_ssm


def init_paged_occupancy(cfg: ModelConfig, layout: PagedLayout, num_pages: dict[str, int] | int):
    """Per-page DynaTran "kv" occupancy side arrays for this config's paged
    state (all-live; see ``kvcache.init_occupancy``)."""
    return init_occupancy(layout, cfg.n_cycles, num_pages)


def paged_copy_pages(
    layout: PagedLayout,
    pools: PagedKV,
    kind: str,
    src: Array,
    dst: Array,
    occupancy: dict[str, Array] | None = None,
) -> tuple[PagedKV, dict[str, Array] | None]:
    """Copy pages ``src[i] -> dst[i]`` in every pool of ``kind`` (all pattern
    slots, all cycles, K and V, int8 scale pools included, and the occupancy
    side arrays when present — bits are page content and must fork with the
    page) — the device half of the scheduler's copy-on-write fork."""
    k, v = dict(pools.k), dict(pools.v)
    occ = dict(occupancy) if occupancy is not None else None
    for i, slot_kind in enumerate(layout.slot_kinds):
        if slot_kind != kind:
            continue
        k[str(i)] = entry_copy_pages(k[str(i)], src, dst)
        v[str(i)] = entry_copy_pages(v[str(i)], src, dst)
        if occ is not None:
            occ[str(i)] = copy_pool_pages(occ[str(i)], src, dst)
    return PagedKV(k=k, v=v), occ


def paged_extract_pages(
    layout: PagedLayout,
    pools: PagedKV,
    kind: str,
    pages: Array,
    occupancy: dict[str, Array] | None = None,
) -> dict:
    """Gather pages ``pages`` out of every pool of ``kind`` — the device half
    of a host-tier SPILL.  Returns {"k": {slot: payload}, "v": {...}} plus
    "occ" when occupancy side arrays ride along (occupancy bits are page
    content: a restored page must mask the same dead positions or DynaTran
    attention diverges from the replay path).  The engine ``device_get``s
    the result; ``paged_insert_pages`` consumes it unchanged."""
    out: dict[str, dict[str, Any]] = {"k": {}, "v": {}}
    if occupancy is not None:
        out["occ"] = {}
    for i, slot_kind in enumerate(layout.slot_kinds):
        if slot_kind != kind:
            continue
        out["k"][str(i)] = entry_extract_pages(pools.k[str(i)], pages)
        out["v"][str(i)] = entry_extract_pages(pools.v[str(i)], pages)
        if occupancy is not None:
            out["occ"][str(i)] = entry_extract_pages(occupancy[str(i)], pages)
    return out


def paged_insert_pages(
    layout: PagedLayout,
    pools: PagedKV,
    kind: str,
    dst: Array,
    payload: dict,
    occupancy: dict[str, Array] | None = None,
) -> tuple[PagedKV, dict[str, Array] | None]:
    """Scatter a spilled ``payload`` (a ``paged_extract_pages`` result) onto
    pages ``dst[i]`` of every pool of ``kind`` — the device half of a
    host-tier RESTORE.  Padding entries may target ``TRASH_PAGE`` with
    zeroed payload rows (callers pad to bucketed lengths to bound
    retracing)."""
    k, v = dict(pools.k), dict(pools.v)
    occ = dict(occupancy) if occupancy is not None else None
    for i, slot_kind in enumerate(layout.slot_kinds):
        if slot_kind != kind:
            continue
        k[str(i)] = entry_insert_pages(k[str(i)], dst, payload["k"][str(i)])
        v[str(i)] = entry_insert_pages(v[str(i)], dst, payload["v"][str(i)])
        if occ is not None:
            occ[str(i)] = entry_insert_pages(occ[str(i)], dst, payload["occ"][str(i)])
    return PagedKV(k=k, v=v), occ


def paged_rollback_chunk(
    layout: PagedLayout,
    pools: PagedKV,
    tables: dict[str, Array],  # page kind -> [B, budget(kind)] int32
    start: Array,  # [B] int32 — first rejected position (the accepted cache_len)
    n_clear: Array,  # [B] int32 — rejected span size (0 = row untouched)
    width: int,  # static span bound (speculation depth k+1)
    occupancy: dict[str, Array] | None = None,
) -> tuple[PagedKV, dict[str, Array] | None]:
    """Rewind a speculative span: zero K/V (int8: both q and scale pools,
    matching the all-zeros fresh-pool init of ``init_paged_kv``) and re-arm
    occupancy bits to live (matching ``init_occupancy``'s all-ones init) at
    positions ``start[b] .. start[b]+n_clear[b]-1`` of every pattern slot.

    Token parity never needs this — rejected entries sit beyond ``cache_len``,
    are masked by effective length, and are overwritten by the next write to
    their position before any gather can see them.  The zeroing exists for the
    STATE contract: after rollback, full/int8 pools compare bitwise-equal to
    an engine that only ever decoded the accepted prefix (never-written ==
    zeros), and occupancy bits compare equal everywhere.  Ring offsets that
    wrapped (position >= capacity) zero a cell a non-speculating twin still
    holds old out-of-window values in; those cells are unreachable — with
    ``lookahead >= width`` any such overwritten position is already outside
    the attention window and the offset is rewritten by subsequent decode
    before it can re-enter a gather — so ring pools are compared through the
    window mask, not raw.

    ``width`` is static (one trace per speculation depth); ``n_clear`` is a
    runtime leaf, so acceptance-count variation never retraces."""
    k, v = dict(pools.k), dict(pools.v)
    occ = dict(occupancy) if occupancy is not None else None
    span = jnp.arange(width)[None, :]
    for i, slot_kind in enumerate(layout.slot_kinds):
        table = tables[slot_kind]
        p = layout.page_size
        pos = start[:, None] + span  # [B, width]
        valid = span < n_clear[:, None]
        if slot_kind == "ring":
            off = pos % (table.shape[1] * p)
            page = jnp.take_along_axis(table, off // p, axis=1)
            off = off % p
        else:
            maxp = table.shape[1]
            idx = pos // p
            page = jnp.take_along_axis(table, jnp.minimum(idx, maxp - 1), axis=1)
            valid = valid & (idx < maxp)
            off = pos % p

        def zero(pool):
            pg = jnp.where(valid, page, pool.shape[1])  # OOB -> dropped
            return pool.at[:, pg, off].set(0, mode="drop")

        def zero_entry(entry):
            if isinstance(entry, dict):
                return {"q": zero(entry["q"]), "scale": zero(entry["scale"])}
            return zero(entry)

        k[str(i)] = zero_entry(k[str(i)])
        v[str(i)] = zero_entry(v[str(i)])
        if occ is not None:
            pg = jnp.where(valid, page, occ[str(i)].shape[1])
            occ[str(i)] = occ[str(i)].at[:, pg, off].set(True, mode="drop")
    return PagedKV(k=k, v=v), occ


def _ring_ctx_positions(start_len: Array, capacity: int) -> Array:
    """Absolute position held by each ring-buffer offset BEFORE the chunk at
    ``start_len`` is written: offset j holds the largest a <= start_len - 1
    with a % capacity == j (negative = never written)."""
    prev = start_len[:, None] - 1
    j = jnp.arange(capacity)[None, :]
    return prev - ((prev - j) % capacity)


def _paged_attention(
    cfg: ModelConfig,
    layout: PagedLayout,
    i: int,
    q: Array,
    kcache,
    vcache,
    table: Array,
    length: Array,
    *,
    pol: KernelPolicy,
    occ: Array | None = None,  # per-cycle occupancy pool [num_pages, P] bool
) -> Array:
    """Decode attention for one pattern slot against its (just-written)
    pools; ``length`` counts tokens cached BEFORE this step.

    When the policy's "kv" site is live AND a tiled datapath is selected
    (``pol.tiled``), the per-page occupancy bits flow into the attention —
    ``skip=True`` never gathers all-dead pages (the Pallas kernel ``@pl.when``s
    past them; the ref path ``lax.cond``s past them), ``skip=False`` masks the
    same positions through the identical datapath, bit for bit.  Otherwise the
    historical occupancy-blind paths run unchanged.
    """
    ring = layout.slot_kinds[i] == "ring"
    eff_len = jnp.minimum(length + 1, layout.window) if ring else length + 1
    occ_live = occ is not None and pol.wants("kv") and pol.tiled
    if pol.use_pallas:
        from repro.kernels.paged_attention import paged_decode_attention

        quant = isinstance(kcache, dict)
        return paged_decode_attention(
            q,
            kcache["q"] if quant else kcache,
            vcache["q"] if quant else vcache,
            table,
            length + 1,
            k_scale=kcache["scale"] if quant else None,
            v_scale=vcache["scale"] if quant else None,
            window=layout.window if ring else None,
            logit_cap=cfg.attn_logit_cap,
            occupancy=occ if occ_live else None,
            skip=bool(pol.skip) if occ_live else True,
            interpret=pol.interpret,
        )
    if occ_live:
        # the pooled variant gathers pages INSIDE its per-page lax.cond, so a
        # dead page costs neither the pool read nor the dequant nor the MACs
        return attn.paged_skip_decode_pooled(
            q,
            kcache,
            vcache,
            occ,
            table,
            length + 1,
            window=layout.window if ring else None,
            logit_cap=cfg.attn_logit_cap,
            skip=bool(pol.skip),
        )
    if ring:
        k_read = entry_gather_ring(kcache, table, length, layout.window)
        v_read = entry_gather_ring(vcache, table, length, layout.window)
    else:
        k_read = entry_gather(kcache, table)
        v_read = entry_gather(vcache, table)
    return attn.decode_attention(q, k_read, v_read, eff_len, window=None, logit_cap=cfg.attn_logit_cap)


def _paged_block_tail(p: dict, cfg: ModelConfig, norm, hh: Array, attn_out: Array, pol: KernelPolicy) -> Array:
    """A paged step's block after its attention output projection: the
    residual and norms under ``proj``, the feed-forward under ``mlp``."""
    with jax.named_scope("proj"):
        if cfg.post_norms:
            attn_out = norm(p["post_attn_norm"], attn_out)
        hh = hh + attn_out
        x = norm(p["ln2"], hh)
    with jax.named_scope("mlp"):
        mlp_out, _ = _mlp(p, cfg, x, pol)
    with jax.named_scope("proj"):
        if cfg.post_norms:
            mlp_out = norm(p["post_mlp_norm"], mlp_out)
        return hh + mlp_out


def _paged_head(params: dict, cfg: ModelConfig, norm, h: Array) -> Array:
    """Final norm and LM head of a paged step, under ``head``: [B, 1, D]
    hidden states to [B, V] float32 logits."""
    with jax.named_scope("head"):
        h = norm(params["final_norm"], h)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = h @ head.astype(h.dtype)
        logits = softcap(logits.astype(jnp.float32), cfg.final_logit_cap)
        return constrain(logits[:, 0], "logits_2d")


def paged_decode_step(
    params: dict,
    cfg: ModelConfig,
    layout: PagedLayout,
    pools: PagedKV,
    tables: dict[str, Array],  # page kind -> [B, budget(kind)] int32
    length: Array,  # [B] int32 — tokens already cached per row
    tokens: Array,  # [B, 1]
    *,
    occupancy: dict[str, Array] | None = None,  # slot -> [n_cycles, num_pages, P] bool
    ssm=None,  # hybrid side-state from init_paged_ssm (or None)
    live: Array | None = None,  # [B] bool: rows with a decoding request
    policy: KernelPolicy | None = None,
    taus=None,  # deprecated: pass policy=
    use_pallas: bool | None = None,  # deprecated: pass policy=
    tp: tuple[str, int] | None = None,  # set when traced inside shard_map (see make_tp_paged_fns)
) -> tuple[Array, PagedKV, dict[str, Array] | None, Any]:
    """One serve step against the paged cache: logits + updated pools, the
    updated per-page occupancy bits, and updated SSM side-state for hybrid
    models.

    ``occupancy`` carries the DynaTran "kv" site (see ``init_occupancy``):
    when the policy enables it, each scattered key also scatters one liveness
    bit — computed from the FULL key before any TP head slicing — and the
    decode attention consumes the bits to skip all-dead pages.  ``None`` (or
    an inactive policy) reproduces the historical occupancy-blind step and
    returns the occupancy unchanged.

    ``live`` masks the SSM state update to rows that actually decode this
    step: K/V writes of idle rows are trash-routed by their page tables,
    but the recurrent state has no such sink — without the mask a decode
    tick would corrupt the state of a slot whose request is mid-prefill.

    With ``tp`` the pools passed in are per-shard (Hkv/n heads); the step
    slices q/k/v to its head block, runs scatter/gather/attention on the
    shard, and all-gathers the per-head attention outputs — bitwise-equal
    to the unsharded step.

    Its ops carry named scopes, which a device trace reads back: ``proj``
    (norms, q/k/v/o projections, rope), ``attn`` (K/V scatter, paged
    attention, and the layer scan's slicing and write-back of the pools),
    ``mlp`` and ``head`` (final norm, LM head).
    """
    pol = resolve_policy(policy, taus=taus, use_pallas=use_pallas, default_sparsity=cfg.sparsity)
    h = params["embed"][tokens]
    if cfg.embed_scale:
        h = h * jnp.sqrt(float(cfg.d_model)).astype(h.dtype)
    if cfg.pos_kind == "learned":
        h = h + params["pos_embed"][length[:, None] % params["pos_embed"].shape[0]]
    positions = length[:, None]  # [B,1]
    _, norm = make_norm(cfg.norm)

    kv_site = occupancy is not None and pol.wants("kv") and pol.tiled

    def cycle_body(carry, xs):
        hh = carry
        cycle_params, kc, vc, occ_c, ssmc = xs
        new_k, new_v, new_occ, new_ssm = {}, {}, {}, {}
        for i, _pat in enumerate(cfg.attention_pattern):
            p = cycle_params[str(i)]
            table = tables[layout.slot_kinds[i]]
            ring = layout.slot_kinds[i] == "ring"
            with jax.named_scope("proj"):
                _x, q, k1, v1 = _qkv(p, cfg, hh, positions, None)
            with jax.named_scope("attn"):
                occ_i = None
                if kv_site:
                    # liveness bit from the FULL key (pre-TP-slice: every
                    # shard computes the same replicated bit), scattered
                    # exactly where the key lands
                    bit = occupancy_bit(k1[:, 0], pol.tau("kv"))
                    op = scatter_token_ring if ring else scatter_token
                    occ_i = op(occ_c[str(i)], table, length, bit)
                    new_occ[str(i)] = occ_i
                elif occupancy is not None:
                    new_occ[str(i)] = occ_c[str(i)]
                q, k1, v1 = _tp_slice_heads(tp, q, k1, v1)
                kcache = entry_scatter_token(kc[str(i)], table, length, k1[:, 0], ring=ring)
                vcache = entry_scatter_token(vc[str(i)], table, length, v1[:, 0], ring=ring)
                ao = _paged_attention(cfg, layout, i, q, kcache, vcache, table, length, pol=pol, occ=occ_i)
                ao = _tp_gather_heads(tp, ao)
                ao = pol.prune(ao, "attn_out")
            with jax.named_scope("proj"):
                attn_out = jnp.einsum("bshk,hkd->bsd", ao, p["wo"].astype(ao.dtype))
            if cfg.ssm_state:
                ssm_out, s_new = ssm_mix(p["ssm"], norm(p["ssm_ln"], hh), state=ssmc[str(i)])
                attn_out = (attn_out + ssm_out) * 0.5
                if live is not None:
                    s_new = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(live[:, None, None], new, old), s_new, ssmc[str(i)]
                    )
                new_ssm[str(i)] = s_new
            hh = _paged_block_tail(p, cfg, norm, hh, attn_out, pol)
            new_k[str(i)], new_v[str(i)] = kcache, vcache
        return hh, (new_k, new_v, new_occ if occupancy is not None else None,
                    new_ssm if cfg.ssm_state else None)

    xs = (params["blocks"], pools.k, pools.v,
          occupancy if occupancy is not None else jnp.zeros((cfg.n_cycles,)),
          ssm if cfg.ssm_state else jnp.zeros((cfg.n_cycles,)))
    # the scan's per-layer slicing and write-back of the pools is K/V
    # traffic: ``attn``, which the body's own scopes override for its ops
    with jax.named_scope("attn"):
        h, (ks, vs, occs, ssms) = jax.lax.scan(cycle_body, h, xs)
    logits = _paged_head(params, cfg, norm, h)
    return (logits, PagedKV(k=ks, v=vs),
            occs if occupancy is not None else None,
            ssms if cfg.ssm_state else None)


def paged_prefill_chunk(
    params: dict,
    cfg: ModelConfig,
    layout: PagedLayout,
    pools: PagedKV,
    tables: dict[str, Array],  # page kind -> [B, budget(kind)] int32
    start_len: Array,  # [B] int32: tokens already cached per row
    tokens: Array,  # [B, C] — one chunk of prompt tokens per row (right-padded)
    n_valid: Array,  # [B] int32: real tokens in each row's chunk (0 = inactive row)
    *,
    occupancy: dict[str, Array] | None = None,  # slot -> [n_cycles, num_pages, P] bool
    ssm=None,
    fresh: Array | None = None,  # [B] bool: rows (re)starting prefill — their SSM state is zeroed
    policy: KernelPolicy | None = None,
    taus=None,  # deprecated: pass policy=
    tp: tuple[str, int] | None = None,  # set when traced inside shard_map (see make_tp_paged_fns)
) -> tuple[Array, PagedKV, dict[str, Array] | None, Any]:
    """Batched prefill: one jitted call caches a chunk of C prompt tokens
    for EVERY row of an admission batch (rows live at their engine slots, so
    hybrid SSM state stays aligned).  Returns next-token logits at each
    row's last valid position [B, V]; rows with n_valid == 0 write nothing,
    leave their SSM state untouched, and return garbage logits.

    With C == 1 this is op-for-op identical to ``paged_decode_step`` (the
    engine's dense-reference equivalence mode) for every cache flavour.
    With C > 1 outputs match per-token replay up to reduction-order float
    noise — exactly zero for bf16 caches in practice, but int8 caches
    amplify one-ulp hidden-state differences into flipped quantisation
    bins in later layers, so chunked int8 prefill is approximate
    (bounded-divergence; decode remains bitwise).

    When the policy's "kv" site is live, each cached key also records its
    occupancy bit (see ``paged_decode_step``); prefill only *writes* bits —
    they are consumed by the decode attention.  Named scopes as in
    ``paged_decode_step``.
    """
    pol = resolve_policy(policy, taus=taus, default_sparsity=cfg.sparsity)
    b, c = tokens.shape
    h = params["embed"][tokens]  # [B, C, D]
    if cfg.embed_scale:
        h = h * jnp.sqrt(float(cfg.d_model)).astype(h.dtype)
    positions = start_len[:, None] + jnp.arange(c)[None, :]  # [B, C]
    if cfg.pos_kind == "learned":
        h = h + params["pos_embed"][positions % params["pos_embed"].shape[0]]
    valid = jnp.arange(c)[None, :] < n_valid[:, None]  # [B, C]
    _, norm = make_norm(cfg.norm)

    kv_site = occupancy is not None and pol.wants("kv") and pol.tiled

    def cycle_body(carry, xs):
        hh = carry
        cycle_params, kc, vc, occ_c, ssmc = xs
        new_k, new_v, new_occ, new_ssm = {}, {}, {}, {}
        for i, _pat in enumerate(cfg.attention_pattern):
            p = cycle_params[str(i)]
            table = tables[layout.slot_kinds[i]]
            ring = layout.slot_kinds[i] == "ring"
            with jax.named_scope("proj"):
                _x, q, k1, v1 = _qkv(p, cfg, hh, positions, None)
            with jax.named_scope("attn"):
                if kv_site:
                    bit = occupancy_bit(k1, pol.tau("kv"))  # [B, C], full pre-TP key
                    op = scatter_chunk_ring if ring else scatter_chunk
                    new_occ[str(i)] = op(occ_c[str(i)], table, start_len, bit, valid)
                elif occupancy is not None:
                    new_occ[str(i)] = occ_c[str(i)]
                q, k1, v1 = _tp_slice_heads(tp, q, k1, v1)
                if ring and c > 1:
                    # sliding-window chunk: attend to the PRE-chunk ring context
                    # (explicit per-entry absolute positions — ring order is
                    # arbitrary) plus the chunk's own K/V, then commit the chunk.
                    # Ring capacity >= window guarantees every in-window prefix
                    # key is still present, for any chunk size.
                    k_ctx = entry_gather(kc[str(i)], table)
                    v_ctx = entry_gather(vc[str(i)], table)
                    ctx_pos = _ring_ctx_positions(start_len, layout.ring_capacity)
                    kcache = entry_scatter_chunk(kc[str(i)], table, start_len, k1, valid, ring=True)
                    vcache = entry_scatter_chunk(vc[str(i)], table, start_len, v1, valid, ring=True)
                    k_in, v_in = k1, v1
                    if isinstance(kc[str(i)], dict):
                        # quantised cache: the in-chunk keys must carry the same
                        # int8-round-tripped bits the pool (and every later
                        # read) holds, or chunked prefill diverges from replay
                        k_in = dequantize_kv(*quantize_kv(k1))
                        v_in = dequantize_kv(*quantize_kv(v1))
                    ao = attn.ring_chunk_attention(
                        q, k_ctx, v_ctx, ctx_pos, k_in, v_in, start_len, n_valid,
                        window=layout.window, logit_cap=cfg.attn_logit_cap,
                    )
                elif ring:
                    # C == 1: decode-style ring read — bitwise-identical to
                    # ``paged_decode_step`` (chunk_decode_attention at C == 1
                    # is bitwise decode_attention; the ring view enforces the
                    # window exactly as the dense ring buffer does)
                    kcache = entry_scatter_chunk(kc[str(i)], table, start_len, k1, valid, ring=True)
                    vcache = entry_scatter_chunk(vc[str(i)], table, start_len, v1, valid, ring=True)
                    k_read = entry_gather_ring(kcache, table, start_len, layout.window)
                    v_read = entry_gather_ring(vcache, table, start_len, layout.window)
                    ao = attn.chunk_decode_attention(q, k_read, v_read, start_len, logit_cap=cfg.attn_logit_cap)
                else:
                    kcache = entry_scatter_chunk(kc[str(i)], table, start_len, k1, valid, ring=False)
                    vcache = entry_scatter_chunk(vc[str(i)], table, start_len, v1, valid, ring=False)
                    k_read = entry_gather(kcache, table)
                    v_read = entry_gather(vcache, table)
                    ao = attn.chunk_decode_attention(q, k_read, v_read, start_len, logit_cap=cfg.attn_logit_cap)
                ao = _tp_gather_heads(tp, ao)
                ao = pol.prune(ao, "attn_out")
            with jax.named_scope("proj"):
                attn_out = jnp.einsum("bshk,hkd->bsd", ao, p["wo"].astype(ao.dtype))
            if cfg.ssm_state:
                sstate = ssmc[str(i)]
                if fresh is not None:
                    sstate = jax.tree_util.tree_map(
                        lambda s: jnp.where(fresh[:, None, None], jnp.zeros_like(s), s), sstate
                    )
                ssm_out, s_new = ssm_mix(p["ssm"], norm(p["ssm_ln"], hh), state=sstate, n_valid=n_valid)
                attn_out = (attn_out + ssm_out) * 0.5
                new_ssm[str(i)] = s_new
            hh = _paged_block_tail(p, cfg, norm, hh, attn_out, pol)
            new_k[str(i)], new_v[str(i)] = kcache, vcache
        return hh, (new_k, new_v, new_occ if occupancy is not None else None,
                    new_ssm if cfg.ssm_state else None)

    xs = (params["blocks"], pools.k, pools.v,
          occupancy if occupancy is not None else jnp.zeros((cfg.n_cycles,)),
          ssm if cfg.ssm_state else jnp.zeros((cfg.n_cycles,)))
    with jax.named_scope("attn"):  # pool slicing and write-back, as in paged_decode_step
        h, (ks, vs, occs, ssms) = jax.lax.scan(cycle_body, h, xs)
    with jax.named_scope("head"):
        last = jnp.maximum(n_valid - 1, 0)[:, None, None]  # [B,1,1]
        h = jnp.take_along_axis(h, last, axis=1)  # last valid position per row
    logits = _paged_head(params, cfg, norm, h)
    return (logits, PagedKV(k=ks, v=vs),
            occs if occupancy is not None else None,
            ssms if cfg.ssm_state else None)


# ---------------------------------------------------------------------------
# Tensor-parallel paged steps: shard_map wrappers over the functions above.
# The mesh "model" axis carries the KV-head shards of the page pools; the
# host side (allocator, page tables, scheduler, prefix cache) stays global
# because page ids are shard-invariant.
# ---------------------------------------------------------------------------


def check_tp_support(cfg: ModelConfig, n: int) -> None:
    if cfg.kv_heads % n or cfg.heads % n:
        raise ValueError(
            f"tensor parallelism needs kv_heads ({cfg.kv_heads}) and heads "
            f"({cfg.heads}) divisible by the shard count {n}"
        )


def make_tp_paged_fns(
    cfg: ModelConfig, layout: PagedLayout, mesh, axis: str = "model", *, use_pallas: bool | None = None
) -> dict:
    """Build shard_map-wrapped decode/prefill/copy steps for serving over
    ``mesh``'s ``axis`` (size n): pools arrive/leave sharded on their KV-head
    dim, every other operand is replicated — including the occupancy side
    arrays (bits are computed from the full pre-slice key, so every shard
    holds identical copies) and the ``KernelPolicy`` (its taus are runtime
    leaves; its static fields ride the closure) — and the math inside is
    head-sliced so TP decode stays bitwise-identical to the single-device
    step (see the tp notes on ``paged_decode_step``).

    Returned callables mirror the unsharded signatures:

    * ``decode(params, pools, occupancy, tables, length, tokens, ssm, live, policy)``
    * ``prefill(params, pools, occupancy, tables, start, tokens, n_valid, ssm, fresh, policy)``
    * ``copy(pools, occupancy, kind, src, dst)``  (the COW page-fork path)
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import paged_pool_specs

    n = mesh.shape[axis]
    check_tp_support(cfg, n)
    tp = (axis, n)
    if use_pallas is not None:
        warnings.warn(
            "make_tp_paged_fns(use_pallas=) is deprecated; pass backend via the "
            "per-call KernelPolicy", DeprecationWarning, stacklevel=2,
        )

    def _pol(policy):
        pol = policy if policy is not None else KernelPolicy.from_config(cfg.sparsity)
        if use_pallas and not pol.use_pallas:
            pol = dataclasses.replace(pol, backend="pallas")
        return pol

    def decode(params, pools, occupancy, tables, length, tokens, ssm, live, policy):
        specs = paged_pool_specs(pools, axis)

        def body(params, pools, occupancy, tables, length, tokens, ssm, live, policy):
            return paged_decode_step(
                params, cfg, layout, pools, tables, length, tokens,
                occupancy=occupancy, ssm=ssm, live=live, policy=_pol(policy), tp=tp,
            )

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), specs, P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), specs, P(), P()),
            check_vma=False,
        )
        return f(params, pools, occupancy, tables, length, tokens, ssm, live, policy)

    def prefill(params, pools, occupancy, tables, start, tokens, n_valid, ssm, fresh, policy):
        specs = paged_pool_specs(pools, axis)

        def body(params, pools, occupancy, tables, start, tokens, n_valid, ssm, fresh, policy):
            return paged_prefill_chunk(
                params, cfg, layout, pools, tables, start, tokens, n_valid,
                occupancy=occupancy, ssm=ssm, fresh=fresh, policy=_pol(policy), tp=tp,
            )

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), specs, P(), P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), specs, P(), P()),
            check_vma=False,
        )
        return f(params, pools, occupancy, tables, start, tokens, n_valid, ssm, fresh, policy)

    def copy(pools, occupancy, kind, src, dst):
        specs = paged_pool_specs(pools, axis)

        def body(pools, occupancy, src, dst):
            return paged_copy_pages(layout, pools, kind, src, dst, occupancy=occupancy)

        f = jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P(), P(), P()), out_specs=(specs, P()),
            check_vma=False,
        )
        return f(pools, occupancy, src, dst)

    return {"decode": decode, "prefill": prefill, "copy": copy}
