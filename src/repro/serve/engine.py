"""Serving engines: slot-granularity baseline and token-granularity
continuous batching with a paged KV cache.

`ServeEngine` (baseline) keeps one jitted prefill and one jitted decode
step; requests are batched to the configured slot count (continuous
batching at slot granularity: finished rows are replaced between
``generate`` calls only).

`ContinuousServeEngine` rebuilds that loop around a block-paged KV cache
(`repro.models.kvcache`) and a request lifecycle: ``submit()`` takes
per-request `SamplingParams` and returns a handle that streams tokens
(``req.tokens()``) and cancels (``req.cancel()``); sequences are admitted
and evicted every step, prefill chunks interleave with decode batches,
requests sharing a page-aligned prompt prefix link the same physical pages
through a refcounted prefix cache (copy-on-write on any shared write), and
a `RhoController` closes DynaTran's accuracy/throughput knob over queue
depth.  Sampling knobs, like the DynaTran thresholds, enter the jitted
step as runtime per-row scalars — changing a request's temperature /
top-k / top-p / seed never recompiles.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.dynatran import SparsityConfig, ThresholdCalculator
from repro.core.policy import KernelPolicy, derive_draft_policy
from repro.models import transformer as tfm
from repro.models import zoo
from repro.models.kvcache import HostPageStore, PageAllocator, PrefixCache
from repro.serve.sampling import (
    SamplingParams, accept_matched, fill_row, sample_tokens, sampling_tensors,
)
from repro.serve.scheduler import ContinuousScheduler, Request, RhoController, summarize


def _resolve_params(
    sampling: Optional[SamplingParams],
    max_new_tokens: Optional[int],
    eos_id: Optional[int],
    default_temperature: float = 0.0,
) -> SamplingParams:
    """Merge the modern ``SamplingParams`` argument with the legacy
    ``max_new_tokens``/``eos_id`` aliases: an explicit alias wins over the
    params' field, and a non-negative ``eos_id`` joins the stop set."""
    sp = sampling if sampling is not None else SamplingParams(temperature=default_temperature)
    if max_new_tokens is not None:
        sp = dataclasses.replace(sp, max_new_tokens=max_new_tokens)
    if eos_id is not None and eos_id >= 0:
        sp = sp.with_stop(eos_id)
    return sp


def _pow2(n: int) -> int:
    """Next power of two >= n (page-op widths are bucketed to bound
    retracing, as _drain_copies does for COW forks)."""
    m = 1
    while m < n:
        m *= 2
    return m


def _pad_pages(a: np.ndarray, n: int) -> np.ndarray:
    """Zero-pad a spilled payload leaf [n_cycles, pages, ...] to ``n`` pages
    (padding rows scatter onto the trash page, whose content is garbage by
    contract)."""
    if a.shape[1] == n:
        return a
    pad = np.zeros((a.shape[0], n - a.shape[1]) + a.shape[2:], a.dtype)
    return np.concatenate([a, pad], axis=1)


@dataclasses.dataclass
class ServeConfig:
    """Knobs for the slot-granularity baseline engine: ``slots``
    concurrent sequences of up to ``max_len`` tokens, a default sampling
    ``temperature`` (0 = greedy), and the fixed DynaTran ``target_rho``
    (overrides the model config's sparsity target at runtime).
    """

    slots: int = 8  # concurrent sequences
    max_len: int = 512
    temperature: float = 0.0  # default SamplingParams temperature (0 = greedy)
    target_rho: Optional[float] = None  # runtime DynaTran knob (overrides cfg)


class ServeEngine:
    """Slot-granularity batched generation baseline: one dense KV cache
    row per request, whole batches admitted and finished together.  The
    continuous engine below replaces it for serving; it survives as the
    reference the serve bench measures against."""

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig, calculator: Optional[ThresholdCalculator] = None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        sp: SparsityConfig = cfg.sparsity
        calculator = calculator or ThresholdCalculator.default()
        if scfg.target_rho is not None and sp.mode == "dynatran":
            sp = dataclasses.replace(sp, target_rho=scfg.target_rho)
        self.taus = calculator.taus(sp) if sp.mode == "dynatran" else None
        self.policy = KernelPolicy.from_config(sp, self.taus)

        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,), static_argnames=("sample",))
        self._sample = jax.jit(sample_tokens)

    # --- jitted bodies ----------------------------------------------------
    def _prefill_impl(self, params, state, tokens, lengths):
        """Run the full prompt through `forward` and write the caches by
        replaying tokens through decode (cache-exact, O(prompt) decode steps
        would be slow; instead we run forward for logits and then batch-write
        K/V via a scan of decode steps only for cache construction when the
        model family needs it).  For simplicity and exactness the engine
        replays decode steps; prompt lengths are padded to the max.

        Returns each row's logits at ITS OWN last prompt position (the scan
        has cached exactly that row's real tokens at that point), so a short
        row's first token is exact even in a ragged batch.  Later positions
        do write pad K/V into the slot-dense cache, which biases subsequent
        decode attention for short rows — an inherent slot-granularity
        limitation; ragged workloads belong on the continuous engine."""
        def step(carry, t):
            st = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            logits, st = zoo.decode_step(params, self.cfg, st, tok, policy=self.policy)
            return st, logits

        state, logits = jax.lax.scan(step, state, jnp.arange(tokens.shape[1]))
        last = logits[lengths - 1, jnp.arange(tokens.shape[0])]  # [B, V]
        return state, last

    def _decode_impl(self, params, state, tokens, temps, top_ks, top_ps, seeds, steps, *, sample: bool):
        logits, state = zoo.decode_step(params, self.cfg, state, tokens, policy=self.policy)
        sliced = logits[..., : self.cfg.vocab]
        if sample:  # shared keyed sampler (serve/sampling.py)
            next_tok = sample_tokens(sliced, temps, top_ks, top_ps, seeds, steps)
        else:  # pure argmax path: bitwise-identical to the original engine
            next_tok = jnp.argmax(sliced, axis=-1).astype(jnp.int32)
        return state, next_tok, logits

    # --- public API ---------------------------------------------------------
    def generate(
        self,
        prompts: list[list[int]],
        max_new_tokens: Optional[int] = None,
        eos_id: int = -1,
        sampling: Optional[SamplingParams] = None,
    ) -> list[list[int]]:
        """Generate for a batch of prompts (token-id lists).  ``sampling``
        applies to every row (per-request policies need the continuous
        engine); when omitted, ``scfg.temperature`` sets the default and
        decoding is greedy at 0.  An explicit ``max_new_tokens`` overrides
        the sampling params'; omitted, ``sampling.max_new_tokens`` (default
        32) governs."""
        if max_new_tokens is None and sampling is None:
            max_new_tokens = 32
        sp = _resolve_params(sampling, max_new_tokens, eos_id)
        if sampling is None and self.scfg.temperature > 0:
            sp = dataclasses.replace(sp, temperature=self.scfg.temperature)
        B = len(prompts)
        assert B <= self.scfg.slots, "more prompts than slots; queue upstream"
        maxp = max(len(p) for p in prompts)
        toks = np.zeros((B, maxp), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
        lengths = np.array([len(p) for p in prompts], np.int32)
        sample = sp.temperature > 0
        st = sampling_tensors(B)
        for i in range(B):
            fill_row(st, i, sp, 0)

        state = zoo.init_decode_state(self.cfg, B, self.scfg.max_len)
        state, last_logits = self._prefill(self.params, state, jnp.asarray(toks), jnp.asarray(lengths))
        sliced = last_logits[..., : self.cfg.vocab]
        if sample:
            cur = self._sample(
                sliced, st["temps"], st["top_ks"], st["top_ps"], st["seeds"], st["steps"]
            )[:, None]
        else:
            cur = jnp.argmax(sliced, axis=-1).astype(jnp.int32)[:, None]
        outs = [cur]
        for t in range(1, sp.max_new_tokens):
            # fresh per call: the CPU backend may alias np buffers zero-copy,
            # so mutating a previously passed array would race the dispatch
            steps_t = np.full((B,), t, np.int32)
            state, nxt, _ = self._decode(
                self.params, state, cur, st["temps"], st["top_ks"], st["top_ps"], st["seeds"],
                steps_t, sample=sample,
            )
            cur = nxt[:, None]
            outs.append(cur)
        gen = np.concatenate([np.asarray(o) for o in outs], axis=1)
        result = []
        for i in range(B):
            row = gen[i].tolist()
            cut = next((j for j, t in enumerate(row) if t in sp.stop), None)
            if cut is not None:
                row = row[: cut + 1]  # stop token included, as eos_id was
            result.append(row)
        return result


# ---------------------------------------------------------------------------
# Continuous batching over the paged KV cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ContinuousServeConfig:
    """Knobs for the continuous-batching engine.

    Capacity: ``slots`` (decode batch width), ``max_len`` (per-sequence
    token budget), ``page_size`` / ``num_pages`` / ``num_pages_ring``
    (KV paging; 0 sizes a pool for the uncontended worst case), and
    ``prefill_chunk`` / ``decode_window`` (dispatch granularity).
    Datapath: ``use_pallas``, ``tile_skip`` (tri-state; see the field
    comment), ``tp`` / ``mesh`` (tensor parallelism).  Memory tiers:
    ``prefix_caching`` and ``tiering`` / ``host_tier_mb`` (the host
    page tier).  DynaTran: ``target_rho`` or ``adaptive_rho`` with the
    ``rho_*`` / ``depth_*`` controller constants.  Field comments below
    are the authoritative per-knob documentation.
    """

    slots: int = 8  # decode batch width
    max_len: int = 512  # per-sequence token budget (prompt + generated)
    page_size: int = 16  # tokens per KV page
    num_pages: int = 0  # "full" pool size; 0 -> slots * full budget + 1 (uncontended)
    num_pages_ring: int = 0  # "ring" pool size; 0 -> slots * ring budget + 1
    prefill_chunk: int = 16  # prompt tokens cached per (batched) prefill call
    # tokens decoded per host tick (multi-step scheduling).  The scheduler
    # must sync on every emitted token; scanning W steps per jitted call
    # amortises that host round-trip W-fold.  Rows finishing mid-window
    # waste at most W-1 row-steps (their surplus tokens are discarded).
    decode_window: int = 1
    use_pallas: bool = False  # fused Pallas paged-attention kernel instead of the XLA ref path
    # DynaTran tile skipping in the hot kernels.  None (default) keeps the
    # legacy dense datapath (occupancy never allocated; old numerics,
    # bit-for-bit).  True routes decode attention + pruned FFN activations
    # through the tiled kernels and SKIPS all-dead tiles; False runs the
    # identical tiled datapath without skipping (the exact-parity twin used
    # by the regression gate).  Needs "kv" in cfg.sparsity.sites (plus a
    # "kv" transfer curve) for attention-side page skipping.
    tile_skip: Optional[bool] = None
    # tensor parallelism: shard the page pools, the paged gather/scatter,
    # and attention along the KV-head dim over a device mesh's "model" axis
    # (launch/mesh.make_serve_mesh).  The host-side scheduler/allocator/
    # prefix cache stay global — page ids are shard-invariant — and TP
    # decode is bitwise-identical to the single-device engine.  Requires
    # cfg.kv_heads % tp == 0.  ``mesh`` overrides the default (1, tp) mesh.
    tp: int = 1
    mesh: Any = None
    # refcounted shared-prefix page cache.  Auto-disabled when the layout
    # has non-shareable state: ring pages (content depends on the sequence's
    # own write cursor) and hybrid SSM side-state are per-sequence; only
    # all-"full" attention layouts (bf16 or int8 pools) share prefixes.
    prefix_caching: bool = True
    # host-memory page tier: eviction SPILLS a request's page contents to a
    # budgeted host-side store and re-admission RESTORES them (one
    # device_put, O(pages moved)) instead of replaying the whole prompt —
    # replay remains the fallback when the tier is full, the snapshot was
    # LRU-dropped, or the bundle carries slot-dense state (no tier ops).
    # The prefix cache reads through the same tier, so cached chains
    # survive device reclaim.  Auto-disabled (like prefix caching) under
    # ADAPTIVE rho: spilled K/V embed the taus they were written at.
    tiering: bool = True
    host_tier_mb: float = 64.0  # host store budget (MB); <= 0 disables
    # speculative decoding: the draft pass proposes ``speculate`` tokens per
    # ready row per tick and ONE batched verify pass (a scan of k+1 paged
    # decode-semantics steps, op-for-op the sequential step, so int8/bf16
    # decode parity carries over) checks them all; rejected tail entries are
    # rolled back by truncating page links.  0 disables.  ``speculate`` is
    # deliberately STATIC — changing the depth recompiles, like decode_window.
    speculate: int = 0
    # self-speculation draft knob: the draft pass runs the SAME weights
    # through the tiled KernelPolicy datapath with taus resolved at this
    # (typically higher) rho — AccelTran's DynaTran knob as a free draft
    # model.  A runtime leaf: moving it never recompiles.  Ignored unless
    # the model config's sparsity mode is "dynatran".
    draft_rho: float = 0.5
    # cross-speculation: a separate small zoo arch (configs.get_smoke name)
    # drafts with its OWN paged state (same page ids through the same
    # tables, so no extra bookkeeping); its layout must match the target's
    # page kinds/budgets and its vocab must cover the target's.  Forces
    # prefix_caching and tiering off (those tiers move only target pages).
    draft_arch: Optional[str] = None
    target_rho: Optional[float] = None  # fixed DynaTran knob when not adaptive
    adaptive_rho: bool = False  # close the rho loop over queue depth
    rho_min: float = 0.0
    rho_max: float = 0.7
    depth_lo: int = 1
    depth_hi: int = 16
    rho_ema: float = 0.5


class ContinuousServeEngine:
    """Token-granularity continuous batching: every step either decodes one
    token for all ready rows or prefills one chunk for EVERY admitted
    prompt (batched prefill), and the scheduler re-fills freed slots/pages
    immediately.  Per-sequence decode state is whatever the family's
    declared ``StateBundle`` says it is (models/kvcache.py state-kind
    registry): full/ring/int8 page pools, slot-dense SSM or rwkv recurrent
    state, slot-dense encoder cross-KV — the engine iterates the bundle,
    so every family in the zoo that declares one (dense, moe, hybrid,
    pure-SSM rwkv6, encoder-decoder whisper) serves through this engine.

    Request lifecycle: ``submit()`` carries per-request ``SamplingParams``
    and returns a handle; ``handle.tokens()`` streams tokens as engine
    steps emit them, ``handle.cancel()`` releases the request's pages
    immediately.  On all-full-attention layouts, prompts sharing a
    page-aligned prefix link the same physical pages (refcounted,
    copy-on-write) — see ``metrics()['prefix_cache']``.

    At ``target_rho == 0`` (or sparsity mode "none") with greedy requests,
    decode logits are bitwise-identical to the dense-KV `ServeEngine` path —
    the paged read reproduces the dense cache's values in the dense cache's
    order and masks exactly the positions the dense read masks.  Prefix
    sharing preserves this: a full page's K/V is a pure per-position
    function of the token prefix, so shared pages hold exactly the bits the
    request's own prefill would have written.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        scfg: ContinuousServeConfig,
        calculator: Optional[ThresholdCalculator] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        # family serve protocol + declared decode-state bundle: everything
        # below iterates over the bundle's registered state KINDS instead of
        # hard-coding "page pools + optional SSM side-state"
        self.fam = zoo.serve_module(cfg)
        # speculation needs headroom for the verify scan's k+1 provisional
        # writes past cache_len, exactly like multi-step decode windows do
        self._spec_k = int(scfg.speculate)
        lookahead = max(scfg.decode_window, self._spec_k + 1) if self._spec_k else scfg.decode_window
        self.layout = self.fam.serve_layout(cfg, scfg.max_len, scfg.page_size, lookahead=lookahead)
        self.bundle = self.fam.serve_state_bundle(cfg, self.layout)
        if self._spec_k:
            # speculation is rollback-by-truncation over PAGED state; a
            # slot-dense component (hybrid SSM, rwkv6 recurrence, whisper
            # cross-KV) advances cumulatively on every verify step and has
            # no truncation seam — rejected steps would corrupt it
            bkinds = list(self.bundle.kinds())
            if not bkinds or not all(kk.paged for kk in bkinds):
                raise ValueError(
                    f"speculate: family '{cfg.family}' carries slot-dense decode "
                    "state, which cannot rewind rejected draft steps "
                    f"(bundle: {self.bundle.describe()})"
                )
        kinds = self.layout.kinds if self.layout is not None else ()
        if "ring" in kinds and scfg.prefill_chunk > self.layout.ring_capacity:
            # a chunk longer than the ring would scatter two laps into one
            # .at[].set — duplicate indices with unspecified resolution order
            raise ValueError(
                f"prefill_chunk={scfg.prefill_chunk} exceeds the ring capacity "
                f"{self.layout.ring_capacity} (window {self.layout.window}, page {scfg.page_size})"
            )
        self.budgets = {k: self.layout.budget(k) for k in kinds}
        num_pages = {}
        for kind in kinds:
            configured = scfg.num_pages if kind == "full" else scfg.num_pages_ring
            num_pages[kind] = configured or scfg.slots * self.budgets[kind] + 1
        self.allocators = {k: PageAllocator(num_pages[k], scfg.page_size) for k in kinds}
        # prefix sharing is a property of the declared state kinds: every
        # component must be a pure per-position function of the token prefix
        # (``StateBundle.shareable`` — full bf16/int8 pages are, ring pages /
        # SSM state / encoder cross-KV are not), and additionally no ADAPTIVE
        # rho — K/V depend on the DynaTran taus, so pages filled at one rho
        # must not be linked by a request arriving at another (a FIXED rho
        # keeps taus constant for the engine's lifetime, which keeps cached
        # pages consistent)
        # cross-speculation shadows every target page with a draft-pool page
        # under the same id; the prefix cache and the host tier link/move
        # only target pages, which would desynchronise the shadow — both off
        cross = bool(self._spec_k and scfg.draft_arch)
        self.prefix_caching = bool(
            scfg.prefix_caching
            and not cross
            and self.bundle.shareable
            and not (cfg.sparsity.mode == "dynatran" and scfg.adaptive_rho)
        )
        self.prefix_cache = PrefixCache(self.allocators["full"]) if self.prefix_caching else None
        # host page tier (the evict ladder's middle rung).  Gated like the
        # prefix cache on rho consistency — spilled K/V embed the taus they
        # were written at, so an ADAPTIVE rho would restore stale numerics —
        # and on the bundle: every state kind must have registered tier ops
        # (``StateBundle.spillable``); one slot-dense component forces the
        # replay fallback for the whole request.
        self.tiering = bool(
            scfg.tiering
            and not cross
            and scfg.host_tier_mb > 0
            and self.bundle.spillable
            and not (cfg.sparsity.mode == "dynatran" and scfg.adaptive_rho)
        )
        self.host_store = HostPageStore(int(scfg.host_tier_mb * 1e6)) if self.tiering else None
        self.sched = ContinuousScheduler(
            scfg.slots, self.allocators, self.budgets, scfg.max_len,
            prefix_cache=self.prefix_cache, page_size=scfg.page_size,
            host_store=self.host_store,
            spill_fn=self._spill_payload if self.tiering else None,
            restore_fn=self._restore_payload if self.tiering else None,
        )
        if self.prefix_cache is not None and self.tiering:
            # prefix-cache write-behind: reclaimed chain entries spill their
            # page content so later admissions restore instead of re-prefill
            self.prefix_cache.host_store = self.host_store
            self.prefix_cache._spill_page = self._spill_prefix_page
        self.pools = self.fam.init_paged_state(cfg, self.layout, num_pages) if kinds else None
        self.num_pages = num_pages
        # slot-dense components (hybrid SSM side-state, rwkv6 recurrent
        # state, whisper cross-KV) ride per engine slot
        self.slot_state = self.fam.init_slot_state(cfg, scfg.slots)

        # tensor parallelism: pools live KV-head-sharded on the mesh, the
        # jitted steps route through shard_map wrappers; everything host-side
        # (allocators, page tables, prefix cache, scheduler) is untouched.
        # Mesh placement per component comes from the state-kind registry.
        self.mesh = None
        self._tp_fns = None
        if scfg.tp > 1 or scfg.mesh is not None:
            if not hasattr(self.fam, "make_tp_paged_fns"):
                raise NotImplementedError(
                    f"tensor parallelism: family '{cfg.family}' has no TP paged step yet"
                )
            from repro.launch.mesh import make_serve_mesh
            from repro.launch.sharding import state_shardings

            self.mesh = scfg.mesh if scfg.mesh is not None else make_serve_mesh(scfg.tp)
            self.fam.check_tp_support(cfg, self.mesh.shape["model"])
            # backend/skip ride the per-call KernelPolicy, not the TP closure
            self._tp_fns = self.fam.make_tp_paged_fns(cfg, self.layout, self.mesh)
            # weights replicated over the mesh once, here: unplaced weights
            # would be re-replicated into the shard_map on every dispatch
            self.params = jax.device_put(params, NamedSharding(self.mesh, PartitionSpec()))
            if self.pools is not None:
                paged_kind = next(k for k in self.bundle.kinds() if k.paged)
                self.pools = jax.device_put(self.pools, state_shardings(paged_kind, self.pools, self.mesh))
            if self.slot_state is not None:
                slot_kind = next(k for k in self.bundle.kinds() if not k.paged)
                self.slot_state = jax.device_put(
                    self.slot_state, state_shardings(slot_kind, self.slot_state, self.mesh)
                )

        # cross-speculation draft: a separate small zoo model with its OWN
        # paged state, shadowing the target pool page-for-page — the same
        # page ids flow through the same tables, so the scheduler's
        # bookkeeping (grow / evict / truncate journals) covers both pools
        # with zero extra state.  Draft params are freshly initialised here;
        # callers with real draft weights overwrite ``self._draft["params"]``.
        self._draft = None
        if cross:
            from repro.configs import get_smoke

            dcfg = get_smoke(scfg.draft_arch)
            dfam = zoo.serve_module(dcfg)
            dlayout = dfam.serve_layout(dcfg, scfg.max_len, scfg.page_size, lookahead=lookahead)
            dbundle = dfam.serve_state_bundle(dcfg, dlayout)
            if dlayout is None or not all(kk.paged for kk in dbundle.kinds()):
                raise ValueError(
                    f"draft_arch {scfg.draft_arch!r}: draft family carries "
                    "slot-dense state and cannot rewind rejected steps"
                )
            dbudgets = {k: dlayout.budget(k) for k in dlayout.kinds}
            if set(dlayout.kinds) != set(kinds) or any(dbudgets[k] != self.budgets[k] for k in kinds):
                raise ValueError(
                    f"draft_arch {scfg.draft_arch!r}: draft page layout "
                    f"{dbudgets} must match the target's {self.budgets} so "
                    "one page table can index both pools"
                )
            if dcfg.vocab_padded < cfg.vocab:
                raise ValueError(
                    f"draft_arch {scfg.draft_arch!r}: draft vocab {dcfg.vocab_padded} "
                    f"does not cover the target vocab {cfg.vocab}"
                )
            self._draft = {
                "cfg": dcfg,
                "fam": dfam,
                "layout": dlayout,
                "params": zoo.init_params(jax.random.PRNGKey(0), dcfg),
                "pools": dfam.init_paged_state(dcfg, dlayout, num_pages),
            }

        sp: SparsityConfig = cfg.sparsity
        self._dynatran = sp.mode == "dynatran"
        self._sites = sp.sites
        # base kernel policy: static fields (backend/skip/sites) are fixed for
        # the engine's lifetime — only the taus leaves change per tick, so the
        # runtime rho knob never recompiles the jitted steps; ``interpret``
        # comes from the platform (compiled kernels on TPU)
        self.policy = KernelPolicy.from_config(
            sp, None,
            backend="pallas" if scfg.use_pallas else "ref",
            skip=scfg.tile_skip,
        )
        # per-page DynaTran occupancy side arrays (all-live at init) — only
        # materialised when the tiled datapath is on; None rides through the
        # jitted steps otherwise (and for families with no paged KV)
        self.occupancy = (
            self.fam.init_paged_occupancy(cfg, self.layout, self.num_pages)
            if (self.policy.tiled and self.pools is not None
                and hasattr(self.fam, "init_paged_occupancy"))
            else None
        )
        if self.occupancy is not None and self.mesh is not None:
            # bits are computed from the full pre-slice key: replicated
            self.occupancy = jax.device_put(
                self.occupancy, NamedSharding(self.mesh, PartitionSpec())
            )
        calculator = calculator or ThresholdCalculator.default()
        # host-side copies of the transfer curves: the per-step tau lookup is
        # two np.interp calls, no device dispatch
        self._curves = {
            s: (np.asarray(c.rhos, np.float64), np.asarray(c.taus, np.float64))
            for s, c in calculator.curves.items()
        }
        self.rho_ctrl = (
            RhoController(scfg.rho_min, scfg.rho_max, scfg.depth_lo, scfg.depth_hi, scfg.rho_ema)
            if (self._dynatran and scfg.adaptive_rho)
            else None
        )
        base_rho = scfg.target_rho if scfg.target_rho is not None else sp.target_rho
        self._fixed_rho = float(base_rho)
        self.current_rho = self._fixed_rho if self._dynatran else 0.0

        # the weights enter every jitted step as an ARGUMENT: a closed-over
        # array would be embedded in the compiled program as a constant
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1, 2, 3), static_argnames=("sample",))
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1, 2, 3), static_argnames=("sample",))
        # one fused dispatch per speculative tick: draft scan + verify scan
        # + device-side accept/rollback.  ``k`` is static (a depth change
        # recompiles, deliberately); the draft taus ride the draft policy's
        # runtime leaves, so moving ``draft_rho`` reuses this trace — the
        # trace-counter test pins both properties.
        self._spec = jax.jit(
            self._spec_impl, donate_argnums=(1, 2, 3, 4), static_argnames=("sample", "k")
        )
        self._draft_prefill = jax.jit(self._draft_prefill_impl, donate_argnums=(0,))
        self._copy = jax.jit(self._copy_impl, donate_argnums=(0, 1))
        self._admit = jax.jit(self._admit_impl, donate_argnums=(1,))
        # host-tier device halves: extract gathers whole pages for a spill
        # (pools NOT donated — the fetch must not invalidate them), insert
        # scatters a restored payload back (pools donated and rebound)
        self._extract = jax.jit(self._extract_impl, static_argnames=("kind",))
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0, 1), static_argnames=("kind",))
        self._rid = 0
        self._tick = 0
        self._peak_pages_in_use = 0
        self.requests: list[Request] = []
        # monotonic counters (never reset by clear_history): the router
        # aggregates these across replicas, so they must survive the
        # metrics-window trims that keep the request history bounded
        self._total_tokens = 0
        self._total_requests = 0
        self._total_finished = 0
        # speculative counters (monotonic, like total_tokens: clear_history
        # never resets them, so fleet-level acceptance tracking stays exact)
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._draft_rho = float(scfg.draft_rho)
        # rho epoch: bumped by set_target_rho so prefix-cache registration
        # can be gated to pages filled entirely at the current taus
        self._rho_epoch = 0
        # metrics() memoization: any state change bumps the version, and the
        # summarize() aggregation only reruns when the version moved
        self._metrics_ver = 0
        self._metrics_cache: Optional[tuple[int, dict]] = None

    # --- jitted bodies ----------------------------------------------------
    def _decode_impl(
        self, params, pools, ssm, occ, tables, lengths, tokens, live, policy,
        temps, top_ks, top_ps, seeds, steps, *, sample: bool,
    ):
        """Scan ``decode_window`` steps per host round-trip; returns the
        window's tokens [W, B].  Sampling knobs are runtime per-row tensors
        (``steps`` advances inside the scan so every window token draws a
        fresh key); ``sample`` is a static flag so all-greedy batches keep
        the pure argmax path."""

        def body(carry, _):
            pools, ssm, occ, lengths, toks, stp = carry
            logits, pools, occ, ssm = self._step_decode(
                params, pools, ssm, occ, tables, lengths, toks, live, policy
            )
            sliced = logits[..., : self.cfg.vocab]
            if sample:
                nxt = sample_tokens(sliced, temps, top_ks, top_ps, seeds, stp)
            else:
                nxt = jnp.argmax(sliced, axis=-1).astype(jnp.int32)
            return (pools, ssm, occ, lengths + 1, nxt[:, None], stp + 1), nxt

        (pools, ssm, occ, _, _, _), toks = jax.lax.scan(
            body, (pools, ssm, occ, lengths, tokens, steps), None, length=self.scfg.decode_window
        )
        return pools, ssm, occ, toks

    def _step_decode(self, params, pools, ssm, occ, tables, lengths, tokens, live, policy):
        """One model step: the shard_map-wrapped TP path or the plain one.
        Returns ``(logits, pools, occupancy, ssm)`` — the uniform 4-tuple
        every family's paged step now speaks."""
        if self._tp_fns is not None:
            return self._tp_fns["decode"](
                params, pools, occ, tables, lengths, tokens, ssm, live, policy
            )
        return self.fam.paged_decode_step(
            params, self.cfg, self.layout, pools, tables, lengths, tokens,
            occupancy=occ, ssm=ssm, live=live, policy=policy,
        )

    def _step_prefill(self, params, pools, ssm, occ, tables, start, tokens, n_valid, fresh, policy):
        if self._tp_fns is not None:
            return self._tp_fns["prefill"](
                params, pools, occ, tables, start, tokens, n_valid, ssm, fresh, policy
            )
        return self.fam.paged_prefill_chunk(
            params, self.cfg, self.layout, pools, tables, start, tokens, n_valid,
            occupancy=occ, ssm=ssm, fresh=fresh, policy=policy,
        )

    def _spec_impl(
        self, params, pools, ssm, occ, dpools, tables, lengths, tokens, live, policy, draft_policy,
        temps, top_ks, top_ps, seeds, steps, *, sample: bool, k: int,
    ):
        """One speculative tick, fused into a single dispatch: a draft scan
        proposes ``k`` tokens per row, a verify scan replays the pending
        token plus all ``k`` drafts through ``k + 1`` target steps (each
        op-for-op a ``paged_decode_step``, so the per-token bitwise-parity
        contract — bf16 AND int8 — carries over verbatim; a chunk-shaped
        C > 1 verify would not give that for int8), and the rejected tail is
        rolled back on device.

        Coupling: draft step i and verify step i-1 sample with the SAME
        per-row key (both at generated-token index ``steps + i - 1``), and
        the engine emits only the TARGET's samples — so the emitted stream
        is unconditionally the non-speculative stream, greedy and sampled
        rows alike, and acceptance is plain token equality
        (``sampling.accept_matched``).  Verify step j writes position
        ``lengths + j`` BEFORE its attention gather (overwriting the draft's
        provisional entry there), so accepted entries hold exactly the bits
        sequential decode would have written.

        Returns ``(pools, ssm, occ, dpools, target_tokens [k+1, B], m [B])``
        where ``m`` is the per-row accepted-draft count: the host emits
        ``m + 1`` tokens and truncates page links past ``lengths + m + 1``.
        """
        if dpools is None:
            # self-speculation: same weights, draft-rho taus, SHARED pools —
            # every draft write is overwritten by the verify scan before any
            # later step can gather it, so no second KV cache exists
            def dbody(carry, _):
                p_, s_, o_, lens_, toks_, stp = carry
                logits, p_, o_, s_ = self._step_decode(
                    params, p_, s_, o_, tables, lens_, toks_, live, draft_policy
                )
                sliced = logits[..., : self.cfg.vocab]
                if sample:
                    nxt = sample_tokens(sliced, temps, top_ks, top_ps, seeds, stp)
                else:
                    nxt = jnp.argmax(sliced, axis=-1).astype(jnp.int32)
                return (p_, s_, o_, lens_ + 1, nxt[:, None], stp + 1), nxt

            (pools, ssm, occ, _, _, _), draft_toks = jax.lax.scan(
                dbody, (pools, ssm, occ, lengths, tokens, steps), None, length=k
            )
        else:
            # cross-speculation: the draft model keeps its own cache of the
            # accepted sequence.  One EXTRA step (k + 1 total) feeds the
            # last draft so the draft pool has no hole at lengths + k when
            # every draft is accepted; its sampled output is discarded.
            d = self._draft

            def dbody(carry, _):
                dp, lens_, toks_, stp = carry
                logits, dp, _, _ = d["fam"].paged_decode_step(
                    d["params"], d["cfg"], d["layout"], dp, tables, lens_, toks_,
                    occupancy=None, ssm=None, live=live, policy=draft_policy,
                )
                sliced = logits[..., : self.cfg.vocab]
                if sample:
                    nxt = sample_tokens(sliced, temps, top_ks, top_ps, seeds, stp)
                else:
                    nxt = jnp.argmax(sliced, axis=-1).astype(jnp.int32)
                return (dp, lens_ + 1, nxt[:, None], stp + 1), nxt

            (dpools, _, _, _), draft_toks = jax.lax.scan(
                dbody, (dpools, lengths, tokens, steps), None, length=k + 1
            )
            draft_toks = draft_toks[:k]

        # verify: feed [pending, d_1 .. d_k]; step j overwrites position
        # lengths + j, attends through it, and emits the target's token
        vin = jnp.concatenate([tokens.T, draft_toks], axis=0)  # [k+1, B]

        def vbody(carry, tok_in):
            p_, s_, o_, lens_, stp = carry
            logits, p_, o_, s_ = self._step_decode(
                params, p_, s_, o_, tables, lens_, tok_in[:, None], live, policy
            )
            sliced = logits[..., : self.cfg.vocab]
            if sample:
                nxt = sample_tokens(sliced, temps, top_ks, top_ps, seeds, stp)
            else:
                nxt = jnp.argmax(sliced, axis=-1).astype(jnp.int32)
            return (p_, s_, o_, lens_ + 1, stp + 1), nxt

        (pools, ssm, occ, _, _), tgt_toks = jax.lax.scan(
            vbody, (pools, ssm, occ, lengths, steps), vin
        )
        m = accept_matched(draft_toks, tgt_toks[:k])  # [B]
        # device half of rollback: zero the rejected span (positions
        # lengths + m + 1 .. lengths + k) and re-arm its occupancy bits —
        # the scheduler truncates the page links on the host side
        new_len = lengths + m + 1
        n_clear = jnp.where(live, k - m, 0)
        pools, occ = tfm.paged_rollback_chunk(
            self.layout, pools, tables, new_len, n_clear, k, occupancy=occ
        )
        if dpools is not None:
            dpools, _ = tfm.paged_rollback_chunk(
                self._draft["layout"], dpools, tables, new_len, n_clear, k
            )
        return pools, ssm, occ, dpools, tgt_toks, m

    def _draft_prefill_impl(self, dpools, tables, start, tokens, n_valid, policy):
        """Cross-speculation prefill ride-along: cache the same chunk into
        the draft model's pools through the same tables (the draft's logits
        are irrelevant during prefill — only its cache matters)."""
        d = self._draft
        _, dpools, _, _ = d["fam"].paged_prefill_chunk(
            d["params"], d["cfg"], d["layout"], dpools, tables, start, tokens, n_valid,
            occupancy=None, ssm=None, fresh=None, policy=policy,
        )
        return dpools

    def _admit_impl(self, params, slot_state, slot, inputs, policy):
        """Admission-computed slot state (whisper: encoder cross-KV) — the
        family hook writes one slot row; ``slot`` is a traced scalar so
        every slot shares one trace."""
        return self.fam.admit_slot(params, self.cfg, slot_state, slot, policy=policy, **inputs)

    def _prefill_impl(
        self, params, pools, ssm, occ, tables, start, tokens, n_valid, fresh, policy,
        temps, top_ks, top_ps, seeds, *, sample: bool,
    ):
        logits, pools, occ, ssm = self._step_prefill(
            params, pools, ssm, occ, tables, start, tokens, n_valid, fresh, policy
        )
        sliced = logits[..., : self.cfg.vocab]
        if sample:  # a request's FIRST token is sampled at step index 0
            next_tok = sample_tokens(sliced, temps, top_ks, top_ps, seeds, jnp.zeros_like(start))
        else:
            next_tok = jnp.argmax(sliced, axis=-1).astype(jnp.int32)
        return pools, ssm, occ, next_tok

    def _copy_impl(self, pools, occ, src, dst):
        if self._tp_fns is not None:
            return self._tp_fns["copy"](pools, occ, "full", src, dst)
        # layout-generic; occupancy bits are page content and fork with the page
        return tfm.paged_copy_pages(self.layout, pools, "full", src, dst, occupancy=occ)

    def _extract_impl(self, pools, occ, pages, *, kind: str):
        return tfm.paged_extract_pages(self.layout, pools, kind, pages, occupancy=occ)

    def _insert_impl(self, pools, occ, dst, payload, *, kind: str):
        return tfm.paged_insert_pages(self.layout, pools, kind, dst, payload, occupancy=occ)

    # --- decode-state plumbing --------------------------------------------
    def state_bytes(self) -> dict:
        """Device bytes per storage class of the bundle: paged pool bytes
        (scale with live tokens / window) and slot-dense bytes (flat in
        max_len — the O(1)/slot claim for rwkv6 and whisper cross-KV)."""
        slot = sum(
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(self.slot_state)
        )
        return {"paged": self.pools.bytes() if self.pools is not None else 0, "slot": slot}

    # --- runtime DynaTran knob -------------------------------------------
    def _current_policy(self) -> KernelPolicy:
        """The tick's KernelPolicy: the engine's static base policy with this
        tick's taus (resolved from the transfer curves at the controller's
        rho) as runtime leaves — a rho change never recompiles."""
        if not self._dynatran:
            return self.policy
        rho = self.rho_ctrl.update(self.sched.queue_depth) if self.rho_ctrl else self._fixed_rho
        self.current_rho = rho
        taus = {
            s: np.float32(np.interp(rho, *self._curves[s]))
            for s in self._sites
            if s in self._curves
        }
        return self.policy.with_taus(taus)

    def _draft_policy(self, policy: KernelPolicy) -> KernelPolicy:
        """The draft pass's KernelPolicy: ``policy`` with taus re-resolved
        at ``self._draft_rho`` (same treedef, so the draft and verify halves
        of ``_spec_impl`` share one trace and a runtime draft-rho move never
        recompiles).  Identity when the model has no DynaTran knob."""
        return derive_draft_policy(policy, self._curves, self._draft_rho)

    # --- public API -------------------------------------------------------
    def submit(
        self,
        prompt: list[int],
        max_new_tokens: Optional[int] = None,
        eos_id: Optional[int] = None,
        slo_s: Optional[float] = None,
        sampling: Optional[SamplingParams] = None,
        inputs: Optional[dict] = None,
    ) -> Request:
        """Queue one request and return its handle.  ``sampling`` carries
        the per-request decode policy; the legacy ``max_new_tokens`` /
        ``eos_id`` aliases override/extend it when passed.  ``inputs``
        carries per-request inputs the model's state bundle declares beyond
        the prompt (whisper: ``frames`` [F, D]).  The handle streams
        (``.tokens()``) and cancels (``.cancel()``)."""
        assert prompt, "empty prompt"
        inputs = dict(inputs or {})
        missing = [k for k in self.bundle.required_inputs if k not in inputs]
        if missing:
            raise ValueError(
                f"family '{self.cfg.family}' requests need inputs {missing} "
                f"(declared by its state bundle: {self.bundle.describe()})"
            )
        req = Request(
            rid=self._rid, prompt=list(prompt), slo_s=slo_s,
            submit_time=time.perf_counter(),
            params=_resolve_params(sampling, max_new_tokens, eos_id),
            inputs=inputs,
            _engine=self,
        )
        self._rid += 1
        self._total_requests += 1
        self._metrics_ver += 1
        self.sched.submit(req)
        self.requests.append(req)
        return req

    def adopt(self, req: Request) -> Request:
        """Attach a request drained from another replica (router handoff):
        its generated tokens ride along and replay through the standard
        evict+replay path, so resuming here is lossless — greedy and keyed
        sampled streams alike continue bit-exactly.  If the drain attached
        a host-tier snapshot (``req._spill``) and this engine is
        tier-compatible, the snapshot seeds the local host store and
        admission RESTORES the pages instead of replaying — the handoff
        moves O(pages), not O(tokens).  The request keeps its
        router-assigned rid; the local rid counter jumps past it so a later
        ``submit`` can never mint a colliding page-allocator owner id."""
        req._engine = self
        self._rid = max(self._rid, req.rid + 1)
        snap, req._spill = req._spill, None
        if snap is not None and self._adoptable(snap):
            self.host_store.put(("req", req.rid), snap, pages=snap["n_pages"])
        self._total_requests += 1
        self._metrics_ver += 1
        self.sched.submit(req)
        self.requests.append(req)
        return req

    def drain(self) -> list[Request]:
        """Release every in-flight and queued request for handoff (replica
        drain): pages/slots free immediately, replay state resets, and the
        detached requests return in FIFO order for another replica to
        ``adopt``.  Finished requests stay in the local metrics window."""
        out = self.sched.drain()
        for req in out:
            req._engine = None
        alive = set(map(id, out))
        self.requests = [r for r in self.requests if id(r) not in alive]
        self._metrics_ver += 1
        return out

    @property
    def load(self) -> int:
        """Queue-depth estimate for router load leveling: requests queued
        plus requests admitted (decoding or mid-prefill)."""
        return self.sched.queue_depth + self.sched.num_active

    def set_target_rho(self, rho: float) -> None:
        """Fleet-level degradation hook (the router's rho ladder): retarget
        the DynaTran knob for every subsequent tick.  Taus are runtime
        pytree leaves, so this never recompiles.  A retarget bumps the rho
        EPOCH and drops the prefix cache: pages filled at the old taus must
        not be linked by arrivals decoding at the new ones, and requests
        admitted before the bump stop registering their (mixed-rho) pages
        — live sharing stays refcount-correct, consistency stays exact."""
        if not self._dynatran:
            raise ValueError(
                f"set_target_rho: sparsity mode {self.cfg.sparsity.mode!r} has no rho knob"
            )
        if self.rho_ctrl is not None:
            raise ValueError(
                "set_target_rho: engine closes its own rho loop (adaptive_rho=True); "
                "fleet-level control needs adaptive_rho=False replicas"
            )
        rho = float(rho)
        if rho != self._fixed_rho:
            self._rho_epoch += 1
            if self.prefix_cache is not None:
                self.prefix_cache.drop_all()
            if self.host_store is not None:
                # spilled pages embed the OLD taus: evicted requests must
                # replay (refilling at the new taus), not restore
                self.host_store.clear()
        self._fixed_rho = rho
        self._metrics_ver += 1

    def cancel(self, req: Request) -> None:
        """Cancel ``req`` wherever it is in its lifecycle — queued, mid-
        prefill, decoding, or evicted — releasing its slot and page links
        immediately (shared prefix pages survive for their other owners and
        the cache).  Idempotent; finished requests are left untouched."""
        if req.done:
            return
        req.cancelled = True
        self.sched.cancel(req)
        req.finish_time = time.perf_counter()
        self._metrics_ver += 1

    def step(self) -> list[Request]:
        """One engine tick: admissions, then one batched prefill chunk (all
        admitted prompts at once) OR one decode batch (alternating when
        both are pending).  Returns newly finished requests."""
        self._tick += 1
        self._metrics_ver += 1
        self._drain_copies()  # forks queued since the last jitted call
        admitted = self.sched.admit_ready()
        for req in admitted:
            req.rho_epoch = self._rho_epoch
        policy = self._current_policy()
        if self.bundle.admit_compute:
            # admission-computed slot state (whisper cross-KV): one encoder
            # run per admitted request, writing its slot row.  Re-admission
            # after eviction recomputes the same bits, so replay is exact.
            for req in admitted:
                dev_inputs = {k: jnp.asarray(v)[None] for k, v in req.inputs.items()}
                self.slot_state = self._admit(
                    self.params, self.slot_state, np.int32(req.slot), dev_inputs, policy
                )
        prefill_reqs = self.sched.prefill_candidates()
        ready = self.sched.decode_rows()
        finished: list[Request] = []
        if prefill_reqs and (not ready or self._tick % 2 == 1):
            finished += self._prefill_step(prefill_reqs, policy)
        elif ready:
            if self._spec_k:
                finished += self._spec_step(ready, policy)
            else:
                finished += self._decode_step(ready, policy)
        in_use = sum(a.num_pages - 1 - a.free_pages for a in self.allocators.values())
        self._peak_pages_in_use = max(self._peak_pages_in_use, in_use)
        return finished

    def run_until_complete(self, max_steps: int = 1_000_000) -> list[Request]:
        """Step until the queue and every slot drain (or ``max_steps``),
        returning the requests finished along the way."""
        finished = []
        for _ in range(max_steps):
            if not self.sched.queue and not self.sched.active:
                return finished
            finished += self.step()
        raise RuntimeError("run_until_complete: step budget exhausted")

    def generate(
        self,
        prompts: list[list[int]],
        max_new_tokens: Optional[int] = None,
        eos_id: int = -1,
        sampling: Optional[SamplingParams] = None,
        inputs: Optional[list[dict]] = None,
    ) -> list[list[int]]:
        """Baseline-compatible API: submit all prompts, run to completion,
        return generated token lists in submission order.  An explicit
        ``max_new_tokens`` overrides the sampling params'; omitted,
        ``sampling.max_new_tokens`` (default 32) governs.  ``inputs`` is an
        optional per-prompt list of bundle-required input dicts."""
        if max_new_tokens is None and sampling is None:
            max_new_tokens = 32
        reqs = [
            self.submit(p, max_new_tokens, eos_id, sampling=sampling,
                        inputs=inputs[i] if inputs else None)
            for i, p in enumerate(prompts)
        ]
        self.run_until_complete()
        return [r.generated for r in reqs]

    def drop_prefix_cache(self) -> None:
        """Drop every prefix-cache retention ref (shutdown / memory drain):
        once live requests finish, the allocator returns to fully free."""
        if self.prefix_cache is not None:
            self.prefix_cache.drop_all()

    def metrics(self) -> dict:
        """Aggregate metrics, memoized per engine state change: repeated
        calls between steps (a router polls every replica per routing
        decision) reuse the cached dict instead of re-running the
        ``summarize`` aggregation over the whole request history."""
        if self._metrics_cache is not None and self._metrics_cache[0] == self._metrics_ver:
            return self._metrics_cache[1]
        out = summarize(self.requests)
        # monotonic counters: never reset by clear_history(), so fleet-level
        # aggregation across metrics-window trims stays exact
        out["total_tokens"] = self._total_tokens
        out["total_requests"] = self._total_requests
        out["total_finished"] = self._total_finished
        # NOTE: no "sheds" key here — shedding is admission control, which
        # only the router performs; its metrics() carries the counter (the
        # engine used to export a hardcoded 0 stub; see docs/OPERATIONS.md)
        out["rho"] = self.current_rho
        if self._spec_k:
            drafted, accepted = self._spec_drafted, self._spec_accepted
            out["speculative"] = {
                "k": self._spec_k,
                "mode": "cross" if self._draft is not None else "self",
                "draft_rho": self._draft_rho,
                # monotonic (clear_history never resets them)
                "drafted": drafted,
                "accepted": accepted,
                "acceptance_rate": accepted / drafted if drafted else None,
            }
        else:
            out["speculative"] = None
        out["free_pages"] = {k: a.free_pages for k, a in self.allocators.items()}
        out["pages_in_use"] = {k: a.num_pages - 1 - a.free_pages for k, a in self.allocators.items()}
        out["peak_pages_in_use"] = self._peak_pages_in_use
        out["prefix_cache"] = self.prefix_cache.stats() if self.prefix_cache else None
        if self.host_store is not None:
            restores, replays = self.sched.restores, self.sched.tier_replays
            out["host_tier"] = {
                **self.host_store.stats(),
                # monotonic (scheduler-owned, so clear_history never resets them)
                "spills": self.sched.spills,
                "spilled_pages": self.sched.spilled_pages,
                "restores": restores,
                "restored_pages": self.sched.restored_pages,
                "tier_replays": replays,
                # fraction of re-admissions served from the tier; a collapse
                # toward 0 means the budget is too small (see OPERATIONS.md)
                "restore_ratio": restores / (restores + replays) if restores + replays else None,
                "prefix_spills": self.prefix_cache.host_spills if self.prefix_cache else 0,
                "prefix_restores": self.prefix_cache.host_restores if self.prefix_cache else 0,
            }
        else:
            out["host_tier"] = None
        out["cache_bytes"] = self.pools.bytes() if self.pools is not None else 0
        out["cache_bytes_per_shard"] = self.pools.shard_bytes() if self.pools is not None else 0
        out["state_bytes"] = self.state_bytes()
        out["tp"] = self.mesh.shape["model"] if self.mesh is not None else 1
        out["queue_depth"] = self.sched.queue_depth
        if self.occupancy is not None:
            # fraction of live KV positions over the whole pool (unwritten
            # pages are initialised all-live, so this is an upper bound that
            # tightens as the pool fills)
            flat = [np.asarray(v) for v in jax.tree_util.tree_leaves(self.occupancy)]
            total = sum(a.size for a in flat)
            out["kv_occupancy_live"] = float(sum(a.sum() for a in flat)) / max(total, 1)
        else:
            out["kv_occupancy_live"] = None
        self._metrics_cache = (self._metrics_ver, out)
        return out

    def clear_history(self) -> None:
        """Drop finished requests from the metrics window.  Long-lived
        engines should call this after consuming ``metrics()`` — the
        request history grows without bound otherwise.  The monotonic
        ``total_*`` counters and the host-tier spill/restore counters
        (scheduler- and store-owned) survive the trim."""
        self.requests = [r for r in self.requests if not r.done]
        self._metrics_ver += 1

    # --- internals --------------------------------------------------------
    def _drain_copies(self) -> None:
        """Execute queued copy-on-write page forks (device-side page copies)
        before the next jitted call touches the pools.  Lengths are padded
        to a power of two — padding pairs copy the trash page onto itself —
        so retraces stay logarithmic in fork-burst size."""
        copies = self.sched.pending_copies
        if not copies:
            return
        self.sched.pending_copies = []
        n = 1
        while n < len(copies):
            n *= 2
        src = np.zeros((n,), np.int32)
        dst = np.zeros((n,), np.int32)
        for i, (s, d) in enumerate(copies):
            src[i], dst[i] = s, d
        self.pools, self.occupancy = self._copy(
            self.pools, self.occupancy, jnp.asarray(src), jnp.asarray(dst)
        )

    # --- host page tier -----------------------------------------------------
    def _tier_meta(self) -> dict:
        """Compatibility stamp carried on every spilled payload: ``adopt``
        restores a handoff snapshot only when the adopting engine matches
        on every field (otherwise the request replays, which is always
        safe)."""
        return {
            "page_size": self.scfg.page_size,
            "family": self.cfg.family,
            "kv_cache_dtype": self.cfg.kv_cache_dtype,
            "shape": (self.cfg.n_cycles, self.cfg.kv_heads, self.cfg.hd, self.layout.slot_kinds),
            "occ": self.occupancy is not None,
            # spilled K/V embed the taus they were written at; a fixed rho
            # pins them (adaptive rho disables tiering entirely)
            "rho": self._fixed_rho if self._dynatran else None,
        }

    def _spill_payload(self, req: Request) -> Optional[dict]:
        """Scheduler spill hook (device -> host): fetch ``req``'s device
        pages — every paged kind, occupancy bits included — as one numpy
        payload.  Queued COW forks are drained first so the fetched
        contents are final; page counts are bucketed to powers of two
        (padding gathers the trash page, sliced off after the fetch) so
        retraces stay logarithmic in table size."""
        self._drain_copies()
        data = {}
        for kind, table in req.tables.items():
            if not table:
                continue
            pages = np.zeros((_pow2(len(table)),), np.int32)
            pages[: len(table)] = table
            fetched = jax.device_get(
                self._extract(self.pools, self.occupancy, jnp.asarray(pages), kind=kind)
            )
            data[kind] = jax.tree_util.tree_map(lambda a: a[:, : len(table)], fetched)
        if not data:
            return None
        return {"data": data, "meta": self._tier_meta()}

    def _restore_payload(self, payload: dict, tables: dict[str, list[int]]) -> None:
        """Scheduler restore hook (host -> device): upload a spilled payload
        onto freshly allocated pages, EAGERLY — queued COW forks drain
        first, so device page ops always apply in queue order and a
        restored page is never read (or forked) before its content lands.
        Under TP each K/V leaf is ``device_put`` with its pool's KV-head
        sharding, so every restored page slice lands on its owning shard;
        occupancy payloads are per-position and land replicated."""
        self._drain_copies()
        for kind, dst in tables.items():
            data = payload["data"].get(kind)
            if data is None or not dst:
                continue
            n = _pow2(len(dst))
            dpad = np.zeros((n,), np.int32)  # padding scatters to the trash page
            dpad[: len(dst)] = dst
            padded = jax.tree_util.tree_map(lambda a: _pad_pages(a, n), data)
            if self.mesh is not None:
                from repro.launch.sharding import paged_payload_shardings

                padded = jax.device_put(padded, paged_payload_shardings(padded, self.mesh))
            self.pools, self.occupancy = self._insert(
                self.pools, self.occupancy, jnp.asarray(dpad), padded, kind=kind
            )

    def _spill_prefix_page(self, page: int) -> Optional[dict]:
        """PrefixCache write-behind hook: fetch ONE cached "full"-kind
        page's content, shaped exactly like a one-page request spill so the
        standard restore hook uploads it."""
        self._drain_copies()
        fetched = jax.device_get(
            self._extract(self.pools, self.occupancy, jnp.asarray(np.array([page], np.int32)), kind="full")
        )
        return {"data": {"full": fetched}, "meta": self._tier_meta()}

    def _adoptable(self, snap: dict) -> bool:
        """Can this engine restore a snapshot spilled by another replica?
        The meta stamp must match exactly and every per-kind page count
        must fit this engine's budgets."""
        if self.host_store is None:
            return False
        return (
            snap["pages"]["meta"] == self._tier_meta()
            and set(snap["counts"]) <= set(self.budgets)
            and all(n <= self.budgets[k] for k, n in snap["counts"].items())
        )

    def _finish(self, req: Request) -> None:
        req.finish_time = time.perf_counter()
        self._total_finished += 1
        self.sched.finish(req)

    def _tables_for(self, reqs: list[Request]) -> dict[str, jnp.ndarray]:
        """Full-width [slots, budget(kind)] page tables: rows without a
        scheduled request point at the trash page.  Empty for bundles with
        no paged component (rwkv6)."""
        out = {
            kind: np.zeros((self.scfg.slots, self.budgets[kind]), np.int32)
            for kind in self.budgets
        }
        for req in reqs:
            for kind, row in self.sched.page_tables(req).items():
                out[kind][req.slot] = row
        return {kind: jnp.asarray(t) for kind, t in out.items()}

    def _prefill_step(self, reqs: list[Request], policy) -> list[Request]:
        """One jitted call caches a chunk for EVERY admitted prompt; rows
        live at their engine slots so hybrid SSM state stays aligned.
        Shared-prefix rows start at their first uncached position."""
        # incremental sharing (vLLM-style): link pages peers registered
        # since admission — a same-tick burst of identical prompts dedupes
        # here, mid-wave, instead of prefilling every copy to completion.
        # Requests admitted before a ``set_target_rho`` retarget sit in an
        # older rho EPOCH: their pages mix taus, so they neither link nor
        # register cache entries (consistency over reuse).
        for req in reqs:
            if req.rho_epoch == self._rho_epoch:
                self.sched.refresh_prefix(req)
        reqs = [r for r in reqs if not r.ready]  # fully-cached replay: straight to decode
        if not reqs:
            return []
        b, c = self.scfg.slots, self.scfg.prefill_chunk
        toks = np.zeros((b, c), np.int32)
        starts = np.zeros((b,), np.int32)
        nv = np.zeros((b,), np.int32)
        fresh = np.zeros((b,), bool)
        st = sampling_tensors(b)
        sample = False
        for req in reqs:
            chunk = req.replay[req.prefill_pos : req.prefill_pos + c]
            toks[req.slot, : len(chunk)] = chunk
            starts[req.slot] = req.prefill_pos
            nv[req.slot] = len(chunk)
            fresh[req.slot] = req.prefill_pos == 0
            if req.prefill_pos + len(chunk) >= len(req.replay) and not req.generated:
                # this row emits its first token from this call
                fill_row(st, req.slot, req.params, 0)
                sample |= req.params.temperature > 0
        self._drain_copies()
        tables = self._tables_for(reqs)
        self.pools, self.slot_state, self.occupancy, next_tok = self._prefill(
            self.params, self.pools, self.slot_state, self.occupancy, tables,
            jnp.asarray(starts), jnp.asarray(toks), jnp.asarray(nv), jnp.asarray(fresh),
            policy, st["temps"], st["top_ks"], st["top_ps"], st["seeds"], sample=sample,
        )
        if self._draft is not None:
            # cross-spec: the draft caches the same chunk through the same
            # tables (evict + replay rebuilds both pools this way)
            self._draft["pools"] = self._draft_prefill(
                self._draft["pools"], tables, jnp.asarray(starts), jnp.asarray(toks),
                jnp.asarray(nv), self._draft_policy(policy),
            )
        finished: list[Request] = []
        for req in reqs:
            took = int(nv[req.slot])
            req.prefill_pos += took
            req.cache_len = req.prefill_pos
            if req.rho_epoch == self._rho_epoch:
                self.sched.register_prefix(req)  # pages -> cache as each fills
            if req.prefill_pos < len(req.replay):
                continue
            req.ready = True
            if req.generated:  # re-admitted after eviction: resume, don't resample
                req.pending_token = req.generated[-1]
                continue
            tok = int(next_tok[req.slot])
            req.generated.append(tok)
            self._total_tokens += 1
            req.pending_token = tok
            req.first_token_time = time.perf_counter()
            if len(req.generated) >= req.max_new_tokens or tok in req.stop_ids:
                self._finish(req)
                finished.append(req)
        return finished

    def _decode_step(self, ready: list[Request], policy) -> list[Request]:
        window = self.scfg.decode_window
        rows: list[Request] = []
        for req in ready:
            if req.slot is not None and self.sched.grow(req, window):
                rows.append(req)
        rows = [r for r in rows if r.slot is not None]  # grow() may evict peers
        if not rows:
            return []
        b = self.scfg.slots
        lens = np.zeros((b,), np.int32)
        toks = np.zeros((b, 1), np.int32)
        live = np.zeros((b,), bool)
        st = sampling_tensors(b)
        sample = False
        for req in rows:
            lens[req.slot] = req.cache_len
            toks[req.slot, 0] = req.pending_token
            live[req.slot] = True
            fill_row(st, req.slot, req.params, len(req.generated))
            sample |= req.params.temperature > 0
        self._drain_copies()
        self.pools, self.slot_state, self.occupancy, win_tok = self._decode(
            self.params, self.pools, self.slot_state, self.occupancy, self._tables_for(rows),
            jnp.asarray(lens), jnp.asarray(toks), jnp.asarray(live), policy,
            st["temps"], st["top_ks"], st["top_ps"], st["seeds"], jnp.asarray(st["steps"]),
            sample=sample,
        )
        win_tok = np.asarray(win_tok)  # [W, B]
        finished = []
        for req in rows:
            for w in range(window):
                tok = int(win_tok[w, req.slot])
                req.cache_len += 1
                req.generated.append(tok)
                self._total_tokens += 1
                req.pending_token = tok
                if len(req.generated) >= req.max_new_tokens or tok in req.stop_ids:
                    self._finish(req)
                    finished.append(req)
                    break  # surplus window tokens are discarded
        return finished

    def _spec_step(self, ready: list[Request], policy) -> list[Request]:
        """One speculative tick: reserve pages for the verify scan's k + 1
        provisional writes (journaling ring advances for rollback), run the
        fused draft + verify + device-rollback dispatch, emit each row's
        ``m + 1`` verified target tokens, then truncate page links back to
        the accepted length.  Rows that finish mid-span skip the truncate —
        ``_finish`` releases their pages wholesale."""
        k = self._spec_k
        rows: list[Request] = []
        logs: dict[int, list] = {}
        for req in ready:
            log: list = []
            if req.slot is not None and self.sched.grow(req, k + 1, log=log):
                rows.append(req)
                logs[req.rid] = log
        rows = [r for r in rows if r.slot is not None]  # grow() may evict peers
        if not rows:
            return []
        b = self.scfg.slots
        lens = np.zeros((b,), np.int32)
        toks = np.zeros((b, 1), np.int32)
        live = np.zeros((b,), bool)
        st = sampling_tensors(b)
        sample = False
        for req in rows:
            lens[req.slot] = req.cache_len
            toks[req.slot, 0] = req.pending_token
            live[req.slot] = True
            fill_row(st, req.slot, req.params, len(req.generated))
            sample |= req.params.temperature > 0
        self._drain_copies()
        dpools = self._draft["pools"] if self._draft is not None else None
        self.pools, self.slot_state, self.occupancy, dpools, tgt_toks, m = self._spec(
            self.params, self.pools, self.slot_state, self.occupancy, dpools, self._tables_for(rows),
            jnp.asarray(lens), jnp.asarray(toks), jnp.asarray(live),
            policy, self._draft_policy(policy),
            st["temps"], st["top_ks"], st["top_ps"], st["seeds"], jnp.asarray(st["steps"]),
            sample=sample, k=k,
        )
        if self._draft is not None:
            self._draft["pools"] = dpools
        tgt_toks = np.asarray(tgt_toks)  # [k+1, B]
        m = np.asarray(m)  # [B]
        finished: list[Request] = []
        for req in rows:
            mi = int(m[req.slot])
            self._spec_drafted += k
            self._spec_accepted += mi
            done = False
            for j in range(mi + 1):  # the target's tokens, in stream order
                tok = int(tgt_toks[j, req.slot])
                req.cache_len += 1
                req.generated.append(tok)
                self._total_tokens += 1
                req.pending_token = tok
                if len(req.generated) >= req.max_new_tokens or tok in req.stop_ids:
                    self._finish(req)
                    finished.append(req)
                    done = True
                    break  # surplus accepted tokens are discarded
            if not done:
                self.sched.truncate(req, req.cache_len, logs.get(req.rid))
        return finished
