"""Logical->physical sharding rules (MaxText-style, path-driven), organised
around first-class **sharding strategies**.

A `Strategy` names the axes used for each logical role:

* ``fsdp``  — weight-shard axes (ZeRO-3 style; gathered per-layer in the scan)
* ``tp``    — tensor-parallel axes (Megatron column/row split), None = no TP
* ``ep``    — expert-parallel axes for the MoE expert dim
* ``batch`` / ``seq`` — activation batch/sequence axes between blocks

Presets (selected per (arch, shape.kind), overridable per cell — this is the
§Perf hillclimbing lever):

* ``fsdp``   — pure ZeRO-3 over ("data","model") combined, batch over every
               axis.  The production recipe for ≤10B dense *training* on a
               v5e-256: weight all-gathers are amortised over the whole
               batch, no per-layer activation collectives.
* ``tp_sp``  — FSDP over "data", Megatron TP over "model" with sequence
               parallelism between blocks.  The *serving* recipe (prefill/
               decode): no weight gathers on the latency path.
* ``ep``     — MoE training: FSDP over "data", experts over "model",
               all-to-all dispatch.
* ``ep_tp``  — MoE serving: experts over "model", dense parts TP.

One table of path-regex rules maps parameter names to role-placeholder
specs; a divisibility *fitter* prunes any axis assignment a given
architecture's shapes cannot honour (e.g. hymba's 25 heads or whisper's odd
vocab), so every (arch x mesh x strategy) combination lowers.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig

# ---------------------------------------------------------------------------
# fitter
# ---------------------------------------------------------------------------


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def fit_spec(shape: tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Drop (set to None) any spec entry whose mesh-axis product does not
    divide the corresponding dim; multi-axis entries degrade to the longest
    dividing prefix.  Guarantees lowering succeeds."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    fitted: list[Any] = []
    used: set[str] = set()

    def _ok(dim: int, axis) -> bool:
        axes = axis if isinstance(axis, (tuple, list)) else (axis,)
        return dim % _axis_size(mesh, axis) == 0 and not (set(axes) & used)

    for dim, axis in zip(shape, entries):
        if axis is not None and not isinstance(axis, (tuple, list)) and _ok(dim, axis):
            fitted.append(axis)
            used.add(axis)
        elif isinstance(axis, (tuple, list)):
            kept = None
            for cut in range(len(axis), 0, -1):
                sub = tuple(axis[:cut])
                if _ok(dim, sub):
                    kept = sub if len(sub) > 1 else sub[0]
                    used.update(sub)
                    break
            fitted.append(kept)
        else:
            fitted.append(None)
    while fitted and fitted[-1] is None:
        fitted.pop()
    return P(*fitted)


def _named(mesh: Mesh, shape, spec: P) -> NamedSharding:
    return NamedSharding(mesh, fit_spec(tuple(shape), spec, mesh))


def dp_spec(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Strategy:
    """Axis assignment for each logical sharding role."""

    name: str
    fsdp: Any  # weight-shard axes (dim 0-ish of weights)
    tp: Any  # tensor-parallel axes (None = no TP)
    ep: Any  # expert axes for MoE expert dim
    moe_inner: Any  # axes for the D dim of expert weights
    batch: tuple[str, ...]  # activation batch axes
    seq: Any  # activation sequence axes between blocks (SP), or None
    vocab: Any  # embedding/LM-head vocab axes
    head_d: Any = ("data",)  # embedding/LM-head d_model axes (never the vocab axes)


STRATEGIES = ("fsdp", "tp_sp", "ep", "ep_tp")


def make_strategy(name: str, mesh: Mesh) -> Strategy:
    dp = dp_spec(mesh)
    if name == "fsdp":
        # Batch over DP, sequence over "model" (SP), weights ZeRO-3 over both
        # axes.  Batch must NOT shard over "model": the vocab-sharded LM head
        # then sees mismatched token shardings between h and dlogits and
        # GSPMD gathers full-batch f32 logits (measured +25 GiB/dev).
        return Strategy(
            name, fsdp=("data", "model"), tp=None, ep=("model",), moe_inner=("data",),
            batch=dp, seq=("model",), vocab="model",
        )
    if name == "tp_sp":
        return Strategy(
            name, fsdp=("data",), tp=("model",), ep=("model",), moe_inner=("data",),
            batch=dp, seq=("model",), vocab="model",
        )
    if name == "ep":
        # seq over "model" between blocks: the layer-scan carry stack saved
        # for remat is [L, B/dp, S, D] per device — unsharded S measured
        # 32 GiB/dev f32 on mixtral train_4k.
        return Strategy(
            name, fsdp=("data", "model"), tp=None, ep=("model",), moe_inner=("data",),
            batch=dp, seq=("model",), vocab="model",
        )
    if name == "ep_tp":
        return Strategy(
            name, fsdp=("data",), tp=("model",), ep=("model",), moe_inner=("data",),
            batch=dp, seq=("model",), vocab="model",
        )
    raise ValueError(f"unknown strategy {name!r} (have {STRATEGIES})")


def default_strategy_name(cfg: ModelConfig, shape: ShapeConfig) -> str:
    if shape.kind == "train":
        return "ep" if cfg.n_experts else "fsdp"
    return "ep_tp" if cfg.n_experts else "tp_sp"


def strategy_for(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, name: str | None = None) -> Strategy:
    return make_strategy(name or default_strategy_name(cfg, shape), mesh)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

# (path regex, spec builder over (cfg, strategy)).  Leaves under blocks/ are
# stacked with a leading layer/cycle axis; a leading None is prepended
# automatically for those.
_PARAM_RULES: list[tuple[str, Any]] = [
    # FIRST MATCH WINS: family-specific rules (rwkv tm/cm, ssm, moe) must
    # precede the generic attention rules — "tm/wo" would otherwise match
    # the 3-D attention "\bwo$" spec and misfit to replicated (measured
    # 10+ x 2 GiB/dev unsharded opt state on rwkv6-7b).
    # rwkv6 time/channel mix (2-D [D, D'] weights)
    (r"tm/(wr|wk|wv|wg)$", lambda cfg, S: P(S.fsdp, S.tp)),
    (r"tm/wo$", lambda cfg, S: P(S.tp, S.fsdp)),
    (r"tm/mix_w1$|tm/w_lora1$", lambda cfg, S: P(S.fsdp, None)),
    (r"cm/wk$", lambda cfg, S: P(S.fsdp, S.tp)),
    (r"cm/wv$", lambda cfg, S: P(S.tp, S.fsdp)),
    (r"cm/wr$", lambda cfg, S: P(S.fsdp, S.tp)),
    # hymba SSM mixer
    (r"ssm/in_proj$", lambda cfg, S: P(S.fsdp, S.tp)),
    (r"ssm/out_proj$", lambda cfg, S: P(S.tp, S.fsdp)),
    (r"ssm/conv_w$", lambda cfg, S: P(None, S.tp)),
    (r"ssm/x_proj$", lambda cfg, S: P(S.tp, None)),
    # MoE: expert dim over EP axes, expert-FFN D dim over moe_inner
    (r"moe/router$", lambda cfg, S: P(S.fsdp, None)),
    (r"moe/w_up$|moe/w_gate$", lambda cfg, S: P(S.ep, S.moe_inner, None)),
    (r"moe/w_down$", lambda cfg, S: P(S.ep, None, S.moe_inner)),
    # attention projections [D, H, hd] / [H, hd, D]
    (r"\bwq$|\bwk$|\bwv$", lambda cfg, S: P(S.fsdp, S.tp, None)),
    (r"\bwo$", lambda cfg, S: P(S.tp, None, S.fsdp)),
    # dense MLP
    (r"mlp/w_up$|mlp/w_gate$", lambda cfg, S: P(S.fsdp, S.tp)),
    (r"mlp/w_down$", lambda cfg, S: P(S.tp, S.fsdp)),
    # embeddings / heads: vocab over S.vocab always (the head is the single
    # biggest matmul; vocab-sharding keeps logits + CE temporaries sharded)
    (r"^embed$", lambda cfg, S: P(S.vocab, S.head_d)),
    (r"^lm_head$", lambda cfg, S: P(S.head_d, S.vocab)),
    (r"^pos_embed$", lambda cfg, S: P(None, S.head_d)),
    (r"cls_head$", lambda cfg, S: P(S.head_d, None)),
]


def _spec_for_path(cfg: ModelConfig, S: Strategy, path: str, ndim: int, stacked: bool, mesh: Mesh | None = None) -> P:
    spec = None
    for pat, fn in _PARAM_RULES:
        if re.search(pat, path):
            spec = fn(cfg, S)
            break
    if spec is None:
        return P()  # replicate (norm scales, biases, small loras, scalars)
    # MoE width-TP fallback: when the expert count does not divide the EP
    # axes (mixtral: 8 experts on a 16-wide "model" axis), shard the expert
    # FFN *width* over those axes instead — otherwise the [E, C, F] expert
    # hidden states replicate (measured 8.75 GiB/dev f32 per silu site).
    if mesh is not None and cfg.n_experts and re.search(r"moe/w_(up|gate|down)$", path):
        if cfg.n_experts % _axis_size(mesh, S.ep):
            width = S.tp or ("model",)
            if path.endswith("w_down"):
                spec = P(None, width, S.moe_inner)
            else:
                spec = P(None, S.moe_inner, width)
    if stacked:
        spec = P(*((None,) + tuple(spec)))
    return spec


def _leaf_path(path_entries) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path_entries)


def param_shardings(cfg: ModelConfig, abstract_params: Any, mesh: Mesh, strategy: Strategy | None = None) -> Any:
    """Pytree of NamedSharding matching the parameter tree."""
    S = strategy or make_strategy("tp_sp", mesh)

    def one(path_entries, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path_entries]
        path = "/".join(keys)
        stacked = any(k in ("blocks", "enc_blocks", "dec_blocks") for k in keys)
        spec = _spec_for_path(cfg, S, path, leaf.ndim, stacked, mesh)
        return _named(mesh, leaf.shape, spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def opt_shardings(cfg: ModelConfig, abstract_opt: Any, mesh: Mesh, pshard: Any, strategy: Strategy | None = None) -> Any:
    """Optimizer state mirrors the parameter shardings (mu/nu/ef); count is
    replicated."""
    S = strategy or make_strategy("tp_sp", mesh)

    def one(path_entries, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path_entries]
        if keys and keys[0] in ("mu", "nu", "ef"):
            path = "/".join(keys[1:])
            stacked = any(k in ("blocks", "enc_blocks", "dec_blocks") for k in keys)
            spec = _spec_for_path(cfg, S, path, leaf.ndim, stacked, mesh)
            return _named(mesh, leaf.shape, spec)
        return NamedSharding(mesh, P())

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_opt)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# batch / activation / decode-state rules
# ---------------------------------------------------------------------------


def batch_shardings(cfg: ModelConfig, shape: ShapeConfig, specs: dict, mesh: Mesh, strategy: Strategy | None = None) -> dict:
    S = strategy or strategy_for(cfg, shape, mesh)
    bspec = S.batch if len(S.batch) > 1 else S.batch[0]
    sspec = None if S.seq is None else (S.seq if len(S.seq) > 1 else S.seq[0])
    if shape.kind == "decode":
        sspec = None  # a 1-token step has no sequence
    out = {}
    for name, sds in specs.items():
        if name in ("tokens", "labels"):
            out[name] = _named(mesh, sds.shape, P(bspec, sspec))
        elif name in ("embeds", "frames"):
            out[name] = _named(mesh, sds.shape, P(bspec, sspec, None))
        elif name == "positions_3d":
            out[name] = _named(mesh, sds.shape, P(bspec, None, sspec))
        else:
            out[name] = NamedSharding(mesh, P())
    return out


def decode_state_shardings(cfg: ModelConfig, abstract_state: Any, mesh: Mesh, shape: ShapeConfig, strategy: Strategy | None = None) -> Any:
    """KV caches: batch over DP when it divides; otherwise (long_500k, B=1)
    shard the sequence dim over ("data","model").  Cache sequence over
    "model" uniformly — kv-head counts as low as 4 make head-TP unusable."""
    dp = dp_spec(mesh)
    B = shape.global_batch
    batch_shardable = B % _axis_size(mesh, dp) == 0

    def one(path_entries, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path_entries]
        path = "/".join(keys)
        if path.endswith("scale") and leaf.ndim == 4:  # int8 cache scales [C,B,T,Hkv]
            spec = P(None, dp, "model", None) if batch_shardable else P(None, None, ("data", "model"), None)
            return _named(mesh, leaf.shape, spec)
        if leaf.ndim == 5:  # [L/C, B, T, Hkv, hd] attention cache
            if batch_shardable:
                spec = P(None, dp, "model", None, None)
            else:
                spec = P(None, None, ("data", "model"), None, None)
            return _named(mesh, leaf.shape, spec)
        if re.search(r"\bs$", path) and leaf.ndim >= 4:  # rwkv state [L,B,H,N,N]
            spec = P(None, dp, "model", None, None) if batch_shardable else P(None, None, "model", None, None)
            return _named(mesh, leaf.shape, spec)
        if leaf.ndim == 4 and "ssm" in path:  # hymba h [C,B,di,N]
            spec = P(None, dp, "model", None) if batch_shardable else P(None, None, "model", None)
            return _named(mesh, leaf.shape, spec)
        if leaf.ndim == 3:  # x_tm [L,B,D]
            spec = P(None, dp, None) if batch_shardable else P()
            return _named(mesh, leaf.shape, spec)
        if leaf.ndim == 1:  # length [B]
            return _named(mesh, leaf.shape, P(dp) if batch_shardable else P())
        if leaf.ndim == 4:  # hymba conv cache [C,B,K-1,di]
            spec = P(None, dp, None, "model") if batch_shardable else P(None, None, None, "model")
            return _named(mesh, leaf.shape, spec)
        return NamedSharding(mesh, P())

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# Activation sharding constraints (model code calls `constrain(x, kind)`)
# ---------------------------------------------------------------------------


def _act_rules(S: Strategy) -> dict[str, P]:
    dpb = S.batch if len(S.batch) > 1 else S.batch[0]
    seq = None if S.seq is None else (S.seq if len(S.seq) > 1 else S.seq[0])
    # logits batch: never over S.vocab's axes -> strip overlapping axes
    vax = set(S.vocab if isinstance(S.vocab, (tuple, list)) else (S.vocab,))
    lb = tuple(a for a in S.batch if a not in vax) or None
    lbs = lb if (lb is None or len(lb) > 1) else lb[0]
    return {
        # residual stream [B, S, D] between blocks
        "residual": P(dpb, seq, None),
        # logits [B, S, V] / [B, V]: vocab over S.vocab, batch over the rest
        "logits": P(lbs, None, S.vocab),
        "logits_2d": P(lbs, S.vocab),
        # attention activations [B, S, H, hd]: heads over TP axes
        "heads": P(dpb, None, S.tp, None),
        # q/k/v entering attention: sequence GATHERED (None), heads over TP.
        # Without this GSPMD defers the seq all-gather into the flash
        # attention chunk scans — measured 1920 trips x 128 MiB on
        # deepseek-7b train_4k (2.3 TB wire); constraining here hoists one
        # gather per layer instead.
        "attn_qkv": P(dpb, None, S.tp, None),
        # MoE dispatch/bucket tensors [G, E, C, D]: groups over batch axes,
        # experts over EP axes (the fitter drops EP when E doesn't divide)
        "experts": P(dpb, S.ep, None, None),
        "moe_mask": P(dpb, None, S.ep, None),
        # Mamba/SSM inner activations [B, S, d_inner(, N)]: channels over
        # "model" — the time scan is sequential in S but channel-local, so
        # d_inner is the shardable dim (da/dbx are [B,S,di,N] f32: 13.4
        # GiB/dev unsharded on hymba-1.5b)
        "ssm_inner": P(dpb, None, "model", None),
        # MoE combined output [G, g, D] BEFORE the reshape to [B, S, D]:
        # without this GSPMD gathers full-G f32 (8 GiB x 16 layers on the
        # olmoe multi-pod prefill) instead of treating the reshape as local
        "moe_out": P(dpb, None, None),
        # SSM carried state [B, d_inner, N]
        "ssm_state": P(dpb, "model", None),
    }


class _ActCtx:
    def __init__(self, mesh: Mesh, strategy: Strategy, overrides: dict[str, P] | None = None):
        self.mesh = mesh
        self.rules = _act_rules(strategy)
        if overrides:
            self.rules.update(overrides)


_ACT_CONTEXT: contextvars.ContextVar[Any] = contextvars.ContextVar("act_ctx", default=None)


@contextlib.contextmanager
def activation_constraints(mesh: Mesh, strategy: Strategy | None = None, overrides: dict[str, P] | None = None):
    """While active, `constrain(x, kind)` inserts sharding constraints built
    on ``mesh``.  Step builders trace model code under this context; model
    code stays mesh-agnostic (constrain is the identity otherwise)."""
    S = strategy or make_strategy("tp_sp", mesh)
    tok = _ACT_CONTEXT.set(_ActCtx(mesh, S, overrides))
    try:
        yield
    finally:
        _ACT_CONTEXT.reset(tok)


def constrain(x: jax.Array, kind: str) -> jax.Array:
    """Apply the activation-sharding rule ``kind`` to ``x`` (identity when no
    context / unknown kind / spec does not fit)."""
    ctx = _ACT_CONTEXT.get()
    if ctx is None or kind not in ctx.rules:
        return x
    spec = ctx.rules[kind]
    if spec is None:
        return x
    fitted = fit_spec(tuple(x.shape), spec, ctx.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, fitted))


# ---------------------------------------------------------------------------
# Paged-KV pool sharding (the tensor-parallel serve path)
# ---------------------------------------------------------------------------


def paged_pool_spec(leaf, axis: str = "model") -> P:
    """PartitionSpec for one paged-pool leaf, sharded on the KV-head dim.

    K/V pools are [n_cycles, num_pages, P, Hkv, D] (bf16/f32 or int8 "q");
    int8 absmax scale pools are [n_cycles, num_pages, P, Hkv].  Page ids are
    shard-invariant — every shard holds the SAME pages for ITS heads — which
    is what lets the host-side allocator/page tables stay global under TP.
    """
    if leaf.ndim == 5:
        return P(None, None, None, axis, None)
    if leaf.ndim == 4:
        return P(None, None, None, axis)
    raise ValueError(f"unexpected paged-pool leaf rank {leaf.ndim}")


def paged_pool_specs(pools: Any, axis: str = "model") -> Any:
    """Spec pytree matching ``pools`` (a PagedKV or any pool pytree)."""
    return jax.tree_util.tree_map(lambda leaf: paged_pool_spec(leaf, axis), pools)


def paged_pool_shardings(pools: Any, mesh: Mesh, axis: str = "model") -> Any:
    """NamedSharding pytree for ``jax.device_put``-ing pools onto ``mesh``."""
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, paged_pool_spec(leaf, axis)), pools
    )


def paged_payload_spec(leaf, axis: str = "model") -> P:
    """PartitionSpec for one SPILLED-page payload leaf (the host-tier
    restore path): payload leaves keep the pool ranks — K/V [n_cycles, n,
    P, Hkv, D] and int8 scales [n_cycles, n, P, Hkv] shard per KV head like
    their pools, while rank-3 occupancy payloads [n_cycles, n, P] are
    per-POSITION and ride replicated.  ``device_put`` with these specs is
    what lands each restored page slice back on its owning shard."""
    if leaf.ndim == 3:
        return P()
    return paged_pool_spec(leaf, axis)


def paged_payload_shardings(payload: Any, mesh: Mesh, axis: str = "model") -> Any:
    """NamedSharding pytree for ``jax.device_put``-ing a spilled payload
    back onto ``mesh`` (see ``paged_payload_spec``)."""
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, paged_payload_spec(leaf, axis)), payload
    )


def state_shardings(kind: Any, tree: Any, mesh: Mesh, axis: str = "model") -> Any:
    """Mesh placement for ONE decode-state component, derived from the
    state-kind registry (``repro.models.kvcache.STATE_KINDS``): kinds with
    ``tp == "kv_heads"`` (page pools) shard per KV head on ``axis``; kinds
    with ``tp == "replicated"`` (slot-dense SSM / rwkv / cross-KV state)
    ride whole on every shard.  ``kind`` is a ``StateKind`` or its registry
    name.  New state kinds get TP placement here, not in the engine."""
    if isinstance(kind, str):
        from repro.models.kvcache import STATE_KINDS  # function-level: models imports this module

        kind = STATE_KINDS[kind]
    if kind.tp == "kv_heads":
        return paged_pool_shardings(tree, mesh, axis)
    if kind.tp == "replicated":
        return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)
    raise ValueError(f"state kind {kind.name!r}: unknown tp spec {kind.tp!r}")


def logits_sharding(cfg: ModelConfig, mesh: Mesh, batch: int, seq: int | None, strategy: Strategy | None = None) -> NamedSharding:
    S = strategy or make_strategy("tp_sp", mesh)
    rules = _act_rules(S)
    if seq is None:
        return _named(mesh, (batch, cfg.vocab_padded), rules["logits_2d"])
    return _named(mesh, (batch, seq, cfg.vocab_padded), rules["logits"])
