"""Continuous batching vs slot-granularity serving at a skewed request mix.

The slot-granularity `ServeEngine` runs every admitted row for the wave's
longest request, so a few long generations strand short rows as padding.
The paged-KV `ContinuousServeEngine` frees a row the step its request
finishes and admits queued work immediately, so useful-token throughput
tracks occupancy instead of the wave maximum.

Measures tokens/s and p50/p99 request latency for both engines on a
75%-short / 25%-long mix, and verifies the paged decode path is
bitwise-identical to the dense-KV baseline at target_rho=0.

The prefix section measures refcounted shared-prefix page caching on a
shared-system-prompt workload: identical tokens to the uncached run,
cache hit rate > 0, fewer pages in use than the no-sharing baseline, and
a fully drained allocator at shutdown — all asserted.  A cold same-tick
burst additionally pins the vLLM-style incremental registration: identical
prompts submitted together dedupe INSIDE one admission wave (pages relink
mid-prefill), holding fewer pages at prefill completion than the uncached
run while emitting identical tokens.

The TP section shards the engine over an emulated device mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``): tensor-parallel
decode must be bitwise-identical to the single-device engine for every
page kind (full / ring / int8), per-shard pool bytes must equal total/N,
and tokens/s is reported per shard count.  Skipped (reported, not failed)
when only one device is visible.

The sparsity section measures the tiled DynaTran datapath (KernelPolicy
``skip``): the tile-skipping engine must emit tokens identical to its
masked-reference twin at the same taus, tokens/s must RISE with target
rho (the "sparsity pays" claim, gated as the rho=0.5 / rho=0 ratio), and
the fused Pallas decode kernel's per-row page-visit counters must fall
strictly as rho rises.

The speculative section measures speculative decoding through the paged
engine: streams must be bitwise-identical to the non-speculative engine
for every paged kind under forced eviction and with DynaTran draft
pruning live (zero-tolerance ``spec_tokens_exact``), and self-speculation
at draft_rho == rho must beat one-token-per-dispatch decode
(``spec_vs_nonspec`` ratio, hard floor 1.0 downstream).

The tiering section measures the host page tier: eviction spills KV pages
to host memory and re-admission restores them instead of replaying
prefill.  Restored tokens must be bitwise-identical to both the straight
decode and the evict+replay run for every paged kind (zero-tolerance
``tier_restore_exact``), and on a long-prompt re-admission workload the
restore path must beat replay (``restore_vs_replay`` ratio, hard floor
1.0 downstream).
"""
from __future__ import annotations

import dataclasses
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.dynatran import SparsityConfig, ThresholdCalculator, TransferCurve
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine, ServeConfig, ServeEngine
from repro.serve.scheduler import pct as _pct

from .common import banner, save


def _tiny_cfg() -> ModelConfig:
    # big enough that model compute dominates per-call dispatch overhead:
    # the claim under test is the serving schedule, not kernel launch cost
    return ModelConfig(
        name="bench-serve", family="dense", layers=4, d_model=256, heads=8, kv_heads=4,
        d_ff=512, vocab=512, remat="none",
    )


def _ring_cfg() -> ModelConfig:
    # gemma2-style local/global alternation: half the layers page into
    # window-budget ring tables instead of max_len-budget full tables
    return ModelConfig(
        name="bench-serve-ring", family="dense", layers=4, d_model=256, heads=8, kv_heads=4,
        d_ff=512, vocab=512, remat="none",
        attention_pattern=("sliding", "full"), window=32,
    )


def _pool_bytes_by_kind(engine) -> dict:
    """Split the engine's pool bytes into ring vs full slots."""
    out = {"ring": 0, "full": 0}
    for i, kind in enumerate(engine.layout.slot_kinds):
        for entry in (engine.pools.k[str(i)], engine.pools.v[str(i)]):
            out[kind] += sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(entry))
    return out


def _run_ring_section(quick: bool) -> dict:
    """Sliding-window (ring) paging on the continuous engine: correctness
    vs the dense baseline, throughput, and the memory claim — ring pool
    bytes scale with ``window`` while a dense cache scales with max_len."""
    from repro.models.kvcache import cache_bytes

    cfg = _ring_cfg()
    params = zoo.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    slots, window = 4, cfg.window
    n_req = 6 if quick else 16
    new_tokens = 24 if quick else 48
    requests = [(rng.integers(1, 256, size=8).tolist(), new_tokens) for _ in range(n_req)]
    useful = sum(new for _, new in requests)

    base = ServeEngine(cfg, params, ServeConfig(slots=1, max_len=128))
    want = [base.generate([p], max_new_tokens=new)[0] for p, new in requests[:3]]
    eng1 = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=1, max_len=128, page_size=8, prefill_chunk=1)
    )
    got = [eng1.generate([p], max_new_tokens=new)[0] for p, new in requests[:3]]
    bitwise = want == got

    engine = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=slots, max_len=128, page_size=8, prefill_chunk=8)
    )
    engine.generate([p for p, _ in requests[:slots]], max_new_tokens=2)  # jit warmup
    engine.clear_history()
    t0 = time.perf_counter()
    for p, new in requests:
        engine.submit(p, max_new_tokens=new)
    engine.run_until_complete()
    wall = time.perf_counter() - t0

    # memory scaling: ring pool bytes are flat in max_len (window-bound);
    # the dense per-slot cache and the full-attention pool both grow linearly
    scaling = []
    for max_len in (128, 256, 512):
        e = ContinuousServeEngine(
            cfg, params, ContinuousServeConfig(slots=slots, max_len=max_len, page_size=8)
        )
        kinds = _pool_bytes_by_kind(e)
        scaling.append(
            {
                "max_len": max_len,
                "ring_pool_bytes": kinds["ring"],
                "full_pool_bytes": kinds["full"],
                "dense_cache_bytes": cache_bytes(cfg.layers, slots, max_len, cfg.kv_heads, cfg.hd),
            }
        )
    flat = scaling[0]["ring_pool_bytes"] == scaling[-1]["ring_pool_bytes"]
    return {
        "bitwise_identical_rho0": bitwise,
        "tok_per_s": useful / wall,
        "window": window,
        "memory_scaling": scaling,
        "ring_bytes_flat_in_max_len": flat,
    }


def _run_tp_section(quick: bool) -> dict:
    """Tensor-parallel serving over the mesh "model" axis: bitwise parity
    with the single-device engine for every page kind, per-shard pool
    memory = total/N, and tokens/s per shard count.  CPU-emulated meshes
    exercise the whole path; real chips run the same code."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        return {
            "skipped": f"needs >= 2 devices, have {n_dev} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        }
    cfg = _tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    n_req = 6 if quick else 16
    new_tokens = 16 if quick else 32
    requests = [(rng.integers(1, 256, size=8).tolist(), new_tokens) for _ in range(n_req)]
    useful = sum(new for _, new in requests)

    def build(c, p, tp):
        return ContinuousServeEngine(
            c, p, ContinuousServeConfig(slots=4, max_len=128, page_size=8, prefill_chunk=8, tp=tp)
        )

    # bitwise parity at TP>1 for every page kind (greedy decode: identical
    # token streams are the engine-level bitwise claim)
    ring_cfg = _ring_cfg()
    int8_cfg = dataclasses.replace(_tiny_cfg(), name="bench-serve-int8", kv_cache_dtype="int8")
    flavours = {
        "full": (cfg, params),
        "ring": (ring_cfg, zoo.init_params(jax.random.PRNGKey(1), ring_cfg)),
        "int8": (int8_cfg, zoo.init_params(jax.random.PRNGKey(2), int8_cfg)),
    }
    tp_test = 2
    parity = {}
    for kind, (c, p) in flavours.items():
        prompts = [q for q, _ in requests[:4]]
        want = build(c, p, 1).generate(prompts, max_new_tokens=new_tokens)
        got = build(c, p, tp_test).generate(prompts, max_new_tokens=new_tokens)
        parity[kind] = want == got

    # throughput + per-shard memory per shard count.  On an emulated mesh
    # all shards run on one physical CPU, so tokens/s is a schedule sanity
    # number, not a hardware scaling claim — the asserted claims here are
    # parity and the memory split.
    scaling = []
    for tp in (1, 2, 4):
        if tp > n_dev or cfg.kv_heads % tp:
            continue
        eng = build(cfg, params, tp)
        eng.generate([q for q, _ in requests[:4]], max_new_tokens=2)  # jit warmup
        eng.clear_history()
        t0 = time.perf_counter()
        for q, new in requests:
            eng.submit(q, max_new_tokens=new)
        eng.run_until_complete()
        wall = time.perf_counter() - t0
        m = eng.metrics()
        scaling.append(
            {
                "tp": tp,
                "tok_per_s": useful / wall,
                "pool_bytes": m["cache_bytes"],
                "pool_bytes_per_shard": m["cache_bytes_per_shard"],
                "shard_bytes_exact": m["cache_bytes_per_shard"] * tp == m["cache_bytes"],
            }
        )
    return {
        "devices": n_dev,
        "bitwise_identical_tp": parity,
        "scaling": scaling,
    }


def _run_prefix_section(quick: bool) -> dict:
    """Refcounted shared-prefix page caching on a shared-system-prompt
    workload: one warm-up request fills the cache, then concurrent bursts
    link the same physical prompt pages.  Asserted claims: the cached run
    emits IDENTICAL tokens to the same workload with caching disabled, hits
    the cache, holds measurably fewer pages during the bursts, and the
    allocator drains to empty at shutdown (no leaked retention refs)."""
    cfg = _tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(2)
    slots, page_size = 4, 8
    system = rng.integers(1, 256, size=32).tolist()  # 4 full pages of shared prefix
    n_req = 8 if quick else 24
    new_tokens = 8 if quick else 16
    tails = [rng.integers(1, 256, size=4).tolist() for _ in range(n_req)]
    warmup = system + rng.integers(1, 256, size=4).tolist()

    results = {}
    for caching in (False, True):
        eng = ContinuousServeEngine(
            cfg, params,
            ContinuousServeConfig(slots=slots, max_len=128, page_size=page_size,
                                  prefill_chunk=8, prefix_caching=caching),
        )
        outs = [eng.generate([warmup], max_new_tokens=new_tokens)[0]]  # fills the cache
        eng.clear_history()
        eng._peak_pages_in_use = 0  # measure the burst phase alone
        t0 = time.perf_counter()
        reqs = [eng.submit(system + tail, max_new_tokens=new_tokens) for tail in tails]
        eng.run_until_complete()
        wall = time.perf_counter() - t0
        outs += [r.generated for r in reqs]
        m = eng.metrics()
        eng.drop_prefix_cache()
        results[caching] = {
            "outs": outs,
            "wall_s": wall,
            "peak_pages_in_use": m["peak_pages_in_use"],
            "prefix_cache": m["prefix_cache"],
            "drained": all(a.free_pages == a.num_pages - 1 for a in eng.allocators.values()),
        }

    # cold same-tick burst (no warm-up): identical prompts submitted
    # together must dedupe INSIDE the admission wave — pages register as
    # each one fills and peers relink them mid-prefill (vLLM-style), so
    # the cached run holds fewer pages by the time every row is decoding
    burst = {}
    burst_tails = tails[: min(6, n_req)]
    for caching in (False, True):
        eng = ContinuousServeEngine(
            cfg, params,
            ContinuousServeConfig(slots=len(burst_tails), max_len=128, page_size=page_size,
                                  prefill_chunk=page_size, prefix_caching=caching),
        )
        reqs = [eng.submit(system + tail, max_new_tokens=new_tokens) for tail in burst_tails]
        in_use_at_ready = None
        for _ in range(100_000):
            if all(r.done for r in reqs):
                break
            eng.step()
            if in_use_at_ready is None and all(r.ready or r.done for r in reqs):
                a = eng.allocators["full"]
                in_use_at_ready = a.num_pages - 1 - a.free_pages
        else:
            raise RuntimeError("cold-burst section: step budget exhausted")
        burst[caching] = {
            "outs": [r.generated for r in reqs],
            "pages_at_ready": in_use_at_ready,
            "relinked_pages": eng.metrics()["prefix_cache"]["relinked_pages"] if caching else 0,
        }

    cached, plain = results[True], results[False]
    stats = cached["prefix_cache"]
    return {
        "requests": n_req + 1,
        "system_prompt_pages": len(system) // page_size,
        "tokens_identical_to_uncached": cached["outs"] == plain["outs"],
        "hit_rate": stats["hit_rate"],
        "pages_shared": stats["pages_shared"],
        "peak_pages_in_use": cached["peak_pages_in_use"],
        "peak_pages_in_use_no_sharing": plain["peak_pages_in_use"],
        "tok_per_s": (n_req * new_tokens) / cached["wall_s"],
        "tok_per_s_no_sharing": (n_req * new_tokens) / plain["wall_s"],
        "allocator_drained_at_shutdown": cached["drained"] and plain["drained"],
        "burst_tokens_identical": burst[True]["outs"] == burst[False]["outs"],
        "burst_relinked_pages": burst[True]["relinked_pages"],
        "burst_pages_at_ready": burst[True]["pages_at_ready"],
        "burst_pages_at_ready_no_sharing": burst[False]["pages_at_ready"],
    }


def _run_families_section(quick: bool) -> dict:
    """The DecodeState-registry families (ISSUE 5): rwkv6 decodes through
    pure slot-dense recurrent state (no pages at all — state bytes flat in
    max_len, asserted) and whisper serves with slot-dense encoder cross-KV
    plus paged decoder self-KV.  Both must emit tokens identical to their
    dense-state replay; rwkv6 tokens/s is additionally gated as a ratio vs
    the same run's slot-granularity engine."""
    from repro import configs as cfg_registry
    from repro.models import whisper as whisper_mod

    out = {}

    # --- rwkv6: slot-dense recurrent state ---------------------------------
    cfg = cfg_registry.get_smoke("rwkv6-7b")
    params = zoo.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(5)
    n_req = 6 if quick else 16
    new_tokens = 12 if quick else 24
    requests = [(rng.integers(1, cfg.vocab, size=8).tolist(), new_tokens) for _ in range(n_req)]
    useful = sum(new for _, new in requests)

    base1 = ServeEngine(cfg, params, ServeConfig(slots=1, max_len=96))
    want = [base1.generate([p], max_new_tokens=new)[0] for p, new in requests[:3]]
    eng1 = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=1, max_len=96, page_size=8, prefill_chunk=1)
    )
    got = [eng1.generate([p], max_new_tokens=new)[0] for p, new in requests[:3]]
    rwkv_match = want == got

    slot_eng = ServeEngine(cfg, params, ServeConfig(slots=4, max_len=96))
    slot_eng.generate([p for p, _ in requests[:4]], max_new_tokens=2)  # jit warmup
    _, _, slot_wall = _run_baseline(slot_eng, requests, slots=4)

    eng = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=4, max_len=96, page_size=8, prefill_chunk=8)
    )
    eng.generate([p for p, _ in requests[:4]], max_new_tokens=2)  # jit warmup
    eng.clear_history()
    t0 = time.perf_counter()
    for p, new in requests:
        eng.submit(p, max_new_tokens=new)
    eng.run_until_complete()
    wall = time.perf_counter() - t0

    # the O(1)-per-slot memory claim: no pages, slot bytes flat in max_len
    small = ContinuousServeEngine(cfg, params, ContinuousServeConfig(slots=4, max_len=96, page_size=8))
    large = ContinuousServeEngine(cfg, params, ContinuousServeConfig(slots=4, max_len=768, page_size=8))
    flat = small.state_bytes() == large.state_bytes() and small.state_bytes()["paged"] == 0
    out["rwkv6"] = {
        "tokens_match_dense": rwkv_match,
        "state_bytes_flat_in_max_len": flat,
        "state_bytes": small.state_bytes(),
        "tok_per_s": useful / wall,
        "slot_tok_per_s": useful / slot_wall,
    }

    # --- whisper: slot-dense cross-KV + paged self-KV ----------------------
    wcfg = cfg_registry.get_smoke("whisper-tiny")
    wparams = zoo.init_params(jax.random.PRNGKey(6), wcfg)
    wrng = np.random.default_rng(6)
    w_req = [(wrng.integers(1, wcfg.vocab, size=8).tolist(), new_tokens) for _ in range(n_req)]
    frames = [
        wrng.standard_normal((wcfg.encoder_frames, wcfg.d_model)).astype(np.float32)
        for _ in w_req
    ]

    w_want = [
        whisper_mod.dense_reference_decode(wparams, wcfg, p, f, new, 96)
        for (p, new), f in zip(w_req[:3], frames[:3])
    ]
    weng1 = ContinuousServeEngine(
        wcfg, wparams, ContinuousServeConfig(slots=1, max_len=96, page_size=8, prefill_chunk=1)
    )
    w_got = weng1.generate(
        [p for p, _ in w_req[:3]], max_new_tokens=new_tokens,
        inputs=[{"frames": f} for f in frames[:3]],
    )
    weng = ContinuousServeEngine(
        wcfg, wparams, ContinuousServeConfig(slots=4, max_len=96, page_size=8, prefill_chunk=8)
    )
    t0 = time.perf_counter()
    reqs = [weng.submit(p, max_new_tokens=new, inputs={"frames": f})
            for (p, new), f in zip(w_req, frames)]
    weng.run_until_complete()
    w_wall = time.perf_counter() - t0
    out["whisper"] = {
        "tokens_match_dense": w_got == w_want,
        "allocator_drained": all(a.free_pages == a.num_pages - 1 for a in weng.allocators.values()),
        "state_bytes": weng.state_bytes(),
        "tok_per_s": sum(new for _, new in w_req) / w_wall,
    }
    assert all(len(r.generated) == new_tokens for r in reqs)
    return out


def _kv_live_share(engine) -> float:
    """Share of the pool's KV positions whose occupancy bit is live
    (unwritten pages start all-live, so it tightens as the pool fills)."""
    bits = [np.asarray(v) for v in jax.tree_util.tree_leaves(engine.occupancy)]
    return sum(float(b.sum()) for b in bits) / max(sum(b.size for b in bits), 1)


def _sparse_cfg() -> ModelConfig:
    # attention-heavy tiny model (long KV read per decoded token, small FFN)
    # so skipped KV pages move the wall clock; "kv" occupancy is opt-in
    return ModelConfig(
        name="bench-serve-sparse", family="dense", layers=2, d_model=256, heads=8, kv_heads=8,
        d_ff=128, vocab=512, remat="none",
        sparsity=SparsityConfig(mode="dynatran", sites=("ffn_act", "attn_out", "kv"), block=16),
    )


def _profiled_calculator(eng: ContinuousServeEngine) -> ThresholdCalculator:
    """Transfer curves for the sparsity section.  The "kv" curve is profiled
    from the probe engine's own filled pools — tau at rho r is the
    r-quantile of the per-position max|k| magnitudes, so ``target_rho``
    maps onto a real dead fraction of the cache regardless of the model's
    activation scale.  The activation sites get modest linear ramps."""
    mags = []
    for i in range(len(eng.layout.slot_kinds)):
        pool = np.asarray(jax.tree_util.tree_leaves(eng.pools.k[str(i)])[0])
        # pool is [n_cycles, num_pages, P, Hkv, D]: per-position max|k| is the
        # max over the trailing (Hkv, D) axes — the occupancy_bit reduction
        m = np.abs(pool).max(axis=(-2, -1)).ravel()
        mags.append(m[m > 0])  # unwritten pool slots are exactly zero
    mags = np.concatenate(mags)
    rhos = np.linspace(0.0, 1.0, 9)
    kv_taus = np.quantile(mags, rhos)
    kv_taus[0] = 0.0  # curve contract: taus[0] == 0 (rho=0 kills nothing)
    return ThresholdCalculator({
        "kv": TransferCurve(taus=jnp.asarray(kv_taus, jnp.float32), rhos=jnp.asarray(rhos, jnp.float32)),
        "ffn_act": TransferCurve(taus=jnp.linspace(0.0, 0.2, 9), rhos=jnp.asarray(rhos, jnp.float32)),
        "attn_out": TransferCurve(taus=jnp.linspace(0.0, 0.05, 9), rhos=jnp.asarray(rhos, jnp.float32)),
    })


def _pallas_visit_counts() -> dict:
    """The fused paged decode kernel's page-visit counters under interpret
    mode: with nested dead-page sets growing with rho, the visited-page
    total must fall STRICTLY as rho rises (the kernel-level skip claim,
    deterministic — no wall clock involved)."""
    from repro.kernels.paged_attention import paged_decode_attention

    rng = np.random.default_rng(7)
    b, maxp, p, hkv, g, d = 2, 6, 4, 2, 2, 16
    num_pages = b * maxp + 1
    pool_k = jnp.asarray(rng.normal(size=(num_pages, p, hkv, d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(num_pages, p, hkv, d)), jnp.float32)
    table = jnp.arange(1, num_pages, dtype=jnp.int32).reshape(b, maxp)
    q = jnp.asarray(rng.normal(size=(b, 1, hkv * g, d)), jnp.float32)
    lengths = jnp.asarray([maxp * p, maxp * p], jnp.int32)

    rhos, visits = (0.0, 0.25, 0.5, 0.75), []
    for rho in rhos:
        # kill the first ceil(rho * (maxp-1)) pages of every row outright:
        # dead sets are nested, so visits must fall strictly with rho (the
        # query's own page is the LAST page and stays live)
        occ = np.ones((num_pages, p), bool)
        dead = int(np.ceil(rho * (maxp - 1)))
        if dead:
            occ[np.asarray(table)[:, :dead].ravel()] = False
        _, n = paged_decode_attention(
            q, pool_k, pool_v, table, lengths,
            occupancy=jnp.asarray(occ), skip=True, with_visits=True, interpret=True,
        )
        visits.append(int(np.asarray(n).sum()))
    dec = all(a > b_ for a, b_ in zip(visits, visits[1:]))
    return {"rhos": list(rhos), "pages_visited": visits, "strictly_decreasing": dec}


def _run_sparsity_section(quick: bool) -> dict:
    """Tile-skipping on the serve path: (1) the skipping engine's tokens are
    IDENTICAL to its masked-reference twin at the same taus (the masked twin
    runs the same tiled datapath without skipping, so any divergence is a
    skip bug, not numerics); (2) tokens/s RISES with target rho — the
    "sparsity pays" claim, gated downstream as the rho=0.5 / rho=0 ratio;
    (3) Pallas visit counters fall strictly with rho."""
    cfg = _sparse_cfg()
    params = zoo.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    prompt_len = 256
    new_tokens = 64
    n_req = 2 if quick else 4
    max_len = 336
    # the rho=0.5 / rho=0 ratio is HARD-floored at 1.0 downstream (same-run,
    # machine-independent), so the sweep shape maximises the attention share
    # of a decode step (long context -> many skippable pages, one-chunk
    # prefill so the identical-across-engines prefill cost doesn't dilute
    # the ratio) and the repeats are INTERLEAVED across rho values, so
    # monotonic machine drift cannot bias one rho's wall
    repeats = 5
    prompts = [rng.integers(1, cfg.vocab, size=prompt_len).tolist() for _ in range(n_req)]
    useful = n_req * new_tokens

    def build(tile_skip, rho, calculator=None):
        # page_size=1 makes every dead position a skippable page, so the page
        # skip fraction tracks rho directly; slots=1 keeps decode B=1 where
        # the per-token KV read dominates the step; decode_window=8 amortises
        # the per-step host dispatch that would otherwise dilute the ratio
        return ContinuousServeEngine(
            cfg, params,
            ContinuousServeConfig(slots=1, max_len=max_len, page_size=1, prefill_chunk=64,
                                  decode_window=8, target_rho=rho, tile_skip=tile_skip),
            calculator=calculator,
        )

    # profile the kv transfer curve off a short legacy-datapath run
    probe = build(None, 0.0)
    probe.generate(prompts[:1], max_new_tokens=4)
    calc = _profiled_calculator(probe)
    del probe

    rho_grid = (0.0, 0.5) if quick else (0.0, 0.25, 0.5, 0.75)
    parity_rho = 0.5
    skip_engines = {rho: build(True, rho, calc) for rho in rho_grid}

    # 1) engine-pair parity at a mid-range rho (greedy: token identity is
    # the engine-level exactness claim)
    mask_eng = build(False, parity_rho, calc)
    want = mask_eng.generate(prompts, max_new_tokens=new_tokens)
    got = skip_engines[parity_rho].generate(prompts, max_new_tokens=new_tokens)
    tile_skip_exact = want == got

    # 2) rho sweep: tokens/s and pool live fraction per target rho, repeats
    # interleaved (rho0 rep1, rho0.5 rep1, rho0 rep2, ...) so host-load drift
    # hits every rho equally
    for rho in rho_grid:
        skip_engines[rho].generate(prompts[:1], max_new_tokens=2)  # jit warmup
        skip_engines[rho].clear_history()
    walls = {rho: float("inf") for rho in rho_grid}
    round_ratios = []  # per-round paired wall(rho0) / wall(rho0.5)

    def sweep_round():
        w = {}
        for rho in rho_grid:
            eng = skip_engines[rho]
            t0 = time.perf_counter()
            for prompt in prompts:
                eng.submit(prompt, max_new_tokens=new_tokens)
            eng.run_until_complete()
            w[rho] = time.perf_counter() - t0
            walls[rho] = min(walls[rho], w[rho])
        round_ratios.append(w[0.0] / w[0.5])

    for _ in range(repeats):
        sweep_round()
    # the gated ratio is the MEDIAN of per-round PAIRED ratios: each round
    # times rho=0 and rho=0.5 back-to-back, so a sustained machine stall
    # multiplies both walls of that round and cancels in the quotient, and
    # the median discards rounds where a transient spike hit only one side.
    # (min-wall tok/s can't do this — it may compare walls from different
    # load epochs.)  when the median still sits near the hard floor, keep
    # sampling rather than gate on a noisy draw
    for _ in range(2):
        if statistics.median(round_ratios) > 1.02:
            break
        for _ in range(repeats):
            sweep_round()
    sweep = []
    for rho in rho_grid:
        sweep.append({"rho": rho, "tok_per_s": useful / walls[rho],
                      "kv_live_frac": _kv_live_share(skip_engines[rho])})
        skip_engines[rho].clear_history()

    return {
        "tile_skip_exact": tile_skip_exact,
        "parity_rho": parity_rho,
        "rho_sweep": sweep,
        "rho05_vs_rho0": statistics.median(round_ratios),
        "rho05_round_ratios": [round(r, 4) for r in round_ratios],
        "pallas_visits": _pallas_visit_counts(),
    }


def _run_router_section(quick: bool) -> dict:
    """Multi-replica router (PR 8): a 2-replica fleet behind the async
    front-end must emit tokens IDENTICAL to the single-engine run on a
    shared-system-prompt workload (greedy rows are independent of
    placement — any divergence is a routing/handoff bug), with affinity
    hit-rate > 0 once the fleet is warm; a replica killed mid-decode must
    replay losslessly through drain + re-admit; and under a flood the
    router must climb the whole rho ladder BEFORE its first shed (ordering
    proven by the rho trace vs the shed tick).  The 2-replica vs single
    tokens/s ratio is a same-run, machine-independent number gated
    downstream."""
    from repro.router import Router, RouterPolicy

    cfg = _tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(8), cfg)
    rng = np.random.default_rng(8)
    page_size = 8
    system = rng.integers(1, 256, size=4 * page_size).tolist()  # 4 shared pages
    n_req = 8 if quick else 24
    new_tokens = 8 if quick else 16
    wave1 = [system + rng.integers(1, 256, size=4).tolist() for _ in range(2)]
    wave2 = [system + rng.integers(1, 256, size=4).tolist() for _ in range(n_req)]
    useful = (len(wave1) + len(wave2)) * new_tokens
    warm_prompt = rng.integers(1, 256, size=8).tolist()  # no shared prefix

    def build(sparsity=None):
        c = cfg if sparsity is None else dataclasses.replace(
            cfg, name="bench-serve-router-dt", sparsity=sparsity
        )
        return ContinuousServeEngine(
            c, params,
            ContinuousServeConfig(slots=4, max_len=128, page_size=page_size, prefill_chunk=8),
        )

    def warmed(eng):
        eng.generate([warm_prompt], max_new_tokens=2)  # jit warmup
        eng.drop_prefix_cache()  # keep the affinity story cold
        eng.clear_history()
        return eng

    # --- single-engine reference: same staged workload, same submission order
    single = warmed(build())
    t0 = time.perf_counter()
    ref_reqs = [single.submit(p, max_new_tokens=new_tokens) for p in wave1]
    single.run_until_complete()
    ref_reqs += [single.submit(p, max_new_tokens=new_tokens) for p in wave2]
    single.run_until_complete()
    single_wall = time.perf_counter() - t0
    ref = [r.generated for r in ref_reqs]

    # --- 2-replica fleet, affinity routing on
    router = Router(
        [warmed(build()), warmed(build())], RouterPolicy(replica_depth_hw=6)
    )
    t0 = time.perf_counter()
    reqs = [router.submit(p, max_new_tokens=new_tokens) for p in wave1]
    router.run_until_complete()
    reqs += [router.submit(p, max_new_tokens=new_tokens) for p in wave2]
    router.run_until_complete()
    router_wall = time.perf_counter() - t0
    got = [r.generated for r in reqs]
    m = router.metrics()

    # --- drain/handoff: kill the loaded replica mid-decode, replay must be
    # lossless (the per-request reference is placement-independent, so the
    # staged run above already pins the expected tokens)
    drain_router = Router(
        [warmed(build()), warmed(build())], RouterPolicy(replica_depth_hw=2)
    )
    dreqs = [drain_router.submit(p, max_new_tokens=new_tokens) for p in wave2[:2]]
    for _ in range(8):  # into decode on both replicas
        drain_router.step()
    victim = next(i for i, h in enumerate(drain_router.replicas) if h.inflight)
    drain_router.health.kill(victim)
    drain_router.run_until_complete()
    router_drain = (
        [r.generated for r in dreqs] == ref[2:4]
        and drain_router.health.failovers == 1
        and all(not r.shed and not r.cancelled for r in dreqs)
    )

    # --- SLO ladder under overload: one dynatran replica, shallow queue cap.
    # Accuracy degrades by design as rho climbs (tokens are NOT compared);
    # the proven claim is the ORDER — every rung announced, saturation
    # reached, and only then the first shed
    ladder_eng = warmed(build(SparsityConfig(mode="dynatran", target_rho=0.0)))
    lrouter = Router(
        [ladder_eng],
        RouterPolicy(replica_depth_hw=2, queue_cap=6, depth_lo=2, depth_hi=8,
                     rho_ema=0.7, slo_p99_ms=200.0),
    )
    flood = 40 if quick else 80
    for _ in range(flood):
        lrouter.submit(rng.integers(1, 256, size=8).tolist(), max_new_tokens=4)
        lrouter.step()
    lrouter.run_until_complete()
    lm = lrouter.metrics()
    # the trace may oscillate AFTER the overload clears (rho stepping back
    # down as backlog drains is the ladder recovering, not a bug); the
    # ordering claim is about the climb: every rung announced, in order,
    # with the top rung reached no later than the first shed
    fst = lm["first_shed_tick"]
    climb = [] if fst is None else [rho for t, rho in lm["rho_trace"] if t <= fst]
    slo_ladder_ordered = lm["sheds"] > 0 and climb == lrouter.ladder.levels

    return {
        "replicas": 2,
        "requests": len(wave1) + len(wave2),
        "router_tokens_exact": got == ref,
        "router_drain": router_drain,
        "slo_ladder_ordered": slo_ladder_ordered,
        "affinity_hits": m["affinity_hits"],
        "affinity_hit_rate": m["affinity_hit_rate"],
        "sheds_parity_run": m["sheds"],
        "tok_per_s": useful / router_wall,
        "single_tok_per_s": useful / single_wall,
        "router2_vs_single": single_wall / router_wall,
        "ladder": {
            "sheds": lm["sheds"],
            "throttles": lm["throttles"],
            "rho_trace": lm["rho_trace"],
            "first_shed_tick": lm["first_shed_tick"],
            "completed": lm["completed"],
        },
    }


def _run_tiering_section(quick: bool) -> dict:
    """Host page tier (KV spill/restore): eviction writes a request's KV
    pages behind to a host-memory store and re-admission restores them with
    one device_put + re-link instead of replaying prefill.  Asserted
    claims: (1) the restored request's tokens are IDENTICAL to both the
    uncontended decode and the evict+replay run, for every paged kind
    (full / int8 / ring) — any divergence is a spill/restore bug, not
    numerics; (2) on a long-prompt re-admission workload the tiering
    engine beats the replay engine — the restore_vs_replay wall-clock
    ratio is HARD-floored at 1.0 downstream (same-run, machine-
    independent, paired-round median like the sparsity ratio)."""
    rng = np.random.default_rng(9)
    exact, activity = {}, {}

    def contended(eng, prompts, new):
        reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        eng.run_until_complete()
        return [r.generated for r in reqs], reqs

    # per-kind parity under forced eviction: full/int8 evict under page
    # pressure on long-ish prompts; ring admits on one page, then first-lap
    # decode growth drains the tight ring pool (same shape as the eviction
    # tests in tests/test_paged_kv.py)
    int8_cfg = dataclasses.replace(_tiny_cfg(), name="bench-serve-tier-int8", kv_cache_dtype="int8")
    ring_cfg = ModelConfig(
        name="bench-serve-tier-ring", family="dense", layers=4, d_model=256, heads=8, kv_heads=4,
        d_ff=512, vocab=512, remat="none",
        attention_pattern=("sliding", "full"), window=8,
    )
    flavours = {
        "full": (_tiny_cfg(), dict(slots=3, num_pages=10), 12, 8),
        "int8": (int8_cfg, dict(slots=3, num_pages=10), 12, 8),
        "ring": (ring_cfg, dict(slots=4, num_pages_ring=7), 2, 16),
    }
    for kind, (c, tight, plen, new) in flavours.items():
        params = zoo.init_params(jax.random.PRNGKey(9), c)
        prompts = [rng.integers(1, 256, size=plen).tolist() for _ in range(5)]
        base = dict(max_len=64, page_size=4, prefill_chunk=4, prefix_caching=False)
        # the uncontended reference must be WIDTH-MATCHED to the contended
        # engines (same slots, default/ample pages -> never evicts): a
        # different decode batch width is a different compiled program, and
        # under --xla_force_host_platform_device_count the GEMM partitioning
        # shifts enough that int8 KV quantization rounds differently — that
        # is cross-width XLA drift, not a spill/restore bug
        straight = ContinuousServeEngine(
            c, params, ContinuousServeConfig(slots=tight["slots"], tiering=False, **base)
        )
        want = [straight.generate([p], max_new_tokens=new)[0] for p in prompts]
        if straight.metrics()["evictions"]:
            raise AssertionError(f"{kind}: reference engine evicted — not an uncontended baseline")
        replay = ContinuousServeEngine(c, params, ContinuousServeConfig(tiering=False, **base, **tight))
        replay_out, rreqs = contended(replay, prompts, new)
        tier = ContinuousServeEngine(c, params, ContinuousServeConfig(host_tier_mb=64.0, **base, **tight))
        tier_out, _ = contended(tier, prompts, new)
        ht = tier.metrics()["host_tier"]
        exact[kind] = want == replay_out == tier_out
        activity[kind] = {"evictions": sum(r.evictions for r in rreqs),
                          "spills": ht["spills"], "restores": ht["restores"]}

    # restore-vs-replay speedup: long prompts make replay (re-prefill the
    # whole prompt) expensive while restore stays one host->device copy.
    # Rounds are PAIRED (replay then tier back-to-back) and the gated ratio
    # is the round-ratio median, so machine drift cancels in the quotient
    cfg = _tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(10), cfg)
    # new=16 in BOTH modes: decode growth past the 27-page pool is what
    # forces eviction (quick economizes via fewer repeats, never pressure)
    plen, new = 96, 16
    prompts = [rng.integers(1, 256, size=plen).tolist() for _ in range(4)]
    scfg = dict(slots=2, max_len=128, page_size=8, prefill_chunk=8,
                prefix_caching=False, num_pages=27)
    replay_eng = ContinuousServeEngine(cfg, params, ContinuousServeConfig(tiering=False, **scfg))
    tier_eng = ContinuousServeEngine(cfg, params, ContinuousServeConfig(host_tier_mb=64.0, **scfg))
    repeats = 3 if quick else 5
    round_ratios = []

    def sweep_round():
        w = {}
        for name, eng in (("replay", replay_eng), ("tier", tier_eng)):
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p, max_new_tokens=new)
            eng.run_until_complete()
            w[name] = time.perf_counter() - t0
        round_ratios.append(w["replay"] / w["tier"])

    sweep_round()  # warmup: compiles prefill/decode AND the extract/insert jits
    round_ratios.clear()
    replay_eng.clear_history()
    tier_eng.clear_history()
    for _ in range(repeats):
        sweep_round()
    # median near the hard floor -> keep sampling rather than gate on noise
    for _ in range(2):
        if statistics.median(round_ratios) > 1.05:
            break
        for _ in range(repeats):
            sweep_round()
    ht = tier_eng.metrics()["host_tier"]
    if not ht["restores"] > 0:
        raise AssertionError("tiering ratio workload produced no restores — page pressure mis-tuned")
    return {
        "tier_restore_exact": all(exact.values()) and all(
            a["evictions"] > 0 and a["restores"] > 0 for a in activity.values()
        ),
        "per_kind_exact": exact,
        "per_kind_activity": activity,
        "restore_vs_replay": statistics.median(round_ratios),
        "round_ratios": [round(r, 4) for r in round_ratios],
        "ratio_workload": {"prompt_len": plen, "new_tokens": new, "requests": len(prompts)},
        "host_tier": ht,
    }


def _run_speculative_section(quick: bool) -> dict:
    """Speculative decoding (ISSUE 10): the draft pass proposes k tokens per
    sequence per tick and the target verifies all of them in ONE fused
    dispatch.  Asserted claims: (1) the speculative engine's streams are
    IDENTICAL to the non-speculative engine for every paged kind
    (full / int8 / ring), under forced eviction + replay mid-speculation,
    and with DynaTran draft pruning live (rejections exercise the
    page-rollback path) — any divergence is a rollback bug, not numerics;
    (2) speculation pays: the spec-vs-nonspec tokens/s ratio is HARD-
    floored at 1.0 downstream.  The gated configuration is self-speculation
    at draft_rho == rho (bit-identical draft and target logits -> every
    draft verifies), so one fused dispatch emits k+1 tokens where the
    non-speculative engine emits 1 — the win is host-dispatch
    amortization, the same effect AccelTran buys in hardware by keeping
    the datapath busy across dependent steps."""
    rng = np.random.default_rng(11)
    k = 3
    exact, acceptance = {}, {}

    def streams(c, p, scfg_kw, prompts, new):
        eng = ContinuousServeEngine(c, p, ContinuousServeConfig(**scfg_kw))
        reqs = [eng.submit(q, max_new_tokens=new) for q in prompts]
        eng.run_until_complete()
        return [r.generated for r in reqs], eng.metrics()

    # per-kind parity under page pressure: the tight pools force eviction +
    # replay mid-speculation (replayed requests re-speculate from their
    # restored length), the ring flavour wraps its window during the
    # speculative window
    int8_cfg = dataclasses.replace(_tiny_cfg(), name="bench-serve-spec-int8", kv_cache_dtype="int8")
    ring_cfg = ModelConfig(
        name="bench-serve-spec-ring", family="dense", layers=4, d_model=256, heads=8, kv_heads=4,
        d_ff=512, vocab=512, remat="none",
        attention_pattern=("sliding", "full"), window=8,
    )
    flavours = {
        "full": (_tiny_cfg(), dict(slots=3, num_pages=10), 12, 8),
        "int8": (int8_cfg, dict(slots=3, num_pages=10), 12, 8),
        "ring": (ring_cfg, dict(slots=4, num_pages_ring=7), 2, 16),
    }
    evictions = {}
    for kind, (c, tight, plen, new) in flavours.items():
        params = zoo.init_params(jax.random.PRNGKey(11), c)
        prompts = [rng.integers(1, 256, size=plen).tolist() for _ in range(5)]
        base = dict(max_len=64, page_size=4, prefill_chunk=4,
                    prefix_caching=False, tiering=False, **tight)
        want, m0 = streams(c, params, base, prompts, new)
        got, m1 = streams(c, params, dict(base, speculate=k), prompts, new)
        exact[kind] = want == got
        acceptance[kind] = m1["speculative"]["acceptance_rate"]
        evictions[kind] = m1["evictions"]

    # rejection parity: DynaTran draft pruning live (target rho=0, draft
    # rho=0.7 -> the draft sees pruned logits and mispredicts), so rejected
    # drafts drive the page-rollback path on every tick
    dcfg = _sparse_cfg()
    dparams = zoo.init_params(jax.random.PRNGKey(12), dcfg)
    dprompts = [rng.integers(1, dcfg.vocab, size=16).tolist() for _ in range(3)]
    dbase = dict(slots=3, max_len=96, page_size=4, prefill_chunk=8,
                 prefix_caching=False, target_rho=0.0)
    probe = ContinuousServeEngine(dcfg, dparams, ContinuousServeConfig(**dbase))
    probe.generate(dprompts[:1], max_new_tokens=4)
    calc = _profiled_calculator(probe)
    del probe
    def dyn_streams(kw):
        eng = ContinuousServeEngine(dcfg, dparams, ContinuousServeConfig(**kw), calculator=calc)
        reqs = [eng.submit(q, max_new_tokens=16) for q in dprompts]
        eng.run_until_complete()
        return [r.generated for r in reqs], eng.metrics()
    want, _ = dyn_streams(dbase)
    got, dm = dyn_streams(dict(dbase, speculate=k, draft_rho=0.7))
    exact["dynatran_draft"] = want == got
    acceptance["dynatran_draft"] = dm["speculative"]["acceptance_rate"]

    # cross-model draft: a random-init zoo draft predicts the target's
    # tokens ~never, so EVERY tick rejects and rolls the speculative pages
    # back — the guaranteed-rollback parity angle (correctness must be
    # independent of acceptance; only throughput depends on it)
    ccfg = _tiny_cfg()
    cparams = zoo.init_params(jax.random.PRNGKey(14), ccfg)
    cprompts = [rng.integers(1, 256, size=8).tolist() for _ in range(3)]
    cbase = dict(slots=3, max_len=64, page_size=4, prefill_chunk=4, prefix_caching=False)
    want, _ = streams(ccfg, cparams, cbase, cprompts, 12)
    got, cm = streams(ccfg, cparams, dict(cbase, speculate=k, draft_arch="deepseek-7b"), cprompts, 12)
    exact["cross_draft"] = want == got
    acceptance["cross_draft"] = cm["speculative"]["acceptance_rate"]
    rollbacks_exercised = acceptance["cross_draft"] < 1.0

    # spec-vs-nonspec speedup on a dispatch-dominated workload: a model
    # small enough that per-dispatch host overhead (scheduler bookkeeping,
    # argument staging, jit call) dominates per-step compute — exactly the
    # regime speculation targets.  Self-spec at draft_rho == rho means the
    # draft and target logits are bit-identical, every draft verifies, and
    # one fused dispatch emits k+1 tokens where the baseline emits 1; the
    # k extra draft steps ride inside the same dispatch.  (On a compute-
    # dominated model self-spec costs 2k+1 model steps per k+1 tokens and
    # cannot pay — the win is amortization, not FLOP reduction.)  Rounds
    # are PAIRED (nonspec then spec back-to-back) and the gated ratio is
    # the round-ratio median, so machine drift cancels in the quotient —
    # same protocol as the sparsity and tiering ratios
    cfg = ModelConfig(
        name="bench-serve-spec-tiny", family="dense", layers=2, d_model=64, heads=4,
        kv_heads=4, d_ff=128, vocab=128, remat="none",
    )
    params = zoo.init_params(jax.random.PRNGKey(13), cfg)
    plen, new = 8, 32 if quick else 64
    prompts = [rng.integers(1, cfg.vocab, size=plen).tolist() for _ in range(4)]
    scfg = dict(slots=2, max_len=128, page_size=8, prefill_chunk=8, prefix_caching=False)
    nonspec_eng = ContinuousServeEngine(cfg, params, ContinuousServeConfig(**scfg))
    spec_eng = ContinuousServeEngine(cfg, params, ContinuousServeConfig(speculate=k, **scfg))
    repeats = 3 if quick else 5
    round_ratios = []

    walls = {"nonspec": float("inf"), "spec": float("inf")}
    spec_streams_equal = True

    def sweep_round():
        nonlocal spec_streams_equal
        w, outs = {}, {}
        for name, eng in (("nonspec", nonspec_eng), ("spec", spec_eng)):
            t0 = time.perf_counter()
            reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
            eng.run_until_complete()
            w[name] = time.perf_counter() - t0
            walls[name] = min(walls[name], w[name])
            outs[name] = [r.generated for r in reqs]
        round_ratios.append(w["nonspec"] / w["spec"])
        # the ratio workload doubles as a parity check: every paired round
        # must emit identical greedy streams
        spec_streams_equal = spec_streams_equal and outs["spec"] == outs["nonspec"]

    sweep_round()  # warmup: compiles prefill/decode AND the fused spec scan
    round_ratios.clear()
    for _ in range(repeats):
        sweep_round()
    for _ in range(2):
        if statistics.median(round_ratios) > 1.05:
            break
        for _ in range(repeats):
            sweep_round()
    sm = spec_eng.metrics()["speculative"]
    ticks = sm["drafted"] // k  # speculative dispatches issued
    useful = len(prompts) * new
    return {
        "k": k,
        "spec_tokens_exact": all(exact.values()) and spec_streams_equal
        and rollbacks_exercised and all(e > 0 for e in evictions.values()),
        "per_kind_exact": exact,
        "per_kind_acceptance": acceptance,
        "per_kind_evictions": evictions,
        "acceptance_rate": sm["acceptance_rate"],
        "accepted_tokens_per_step": (sm["accepted"] + ticks) / ticks if ticks else None,
        "tok_per_s": useful / walls["spec"],
        "tok_per_s_nonspec": useful / walls["nonspec"],
        "spec_vs_nonspec": statistics.median(round_ratios),
        "round_ratios": [round(r, 4) for r in round_ratios],
        "ratio_workload": {"prompt_len": plen, "new_tokens": new, "requests": len(prompts)},
    }


def _run_analysis_section() -> bool:
    """Zero-tolerance ``analysis_clean`` flag: the static reprolint checkers
    (retrace / host-device / donation / Pallas) against the committed
    baseline.  Emitting it from bench-smoke means the regression gate and the
    ``lint-invariants`` CI lane enforce the same contract and cannot silently
    drift apart (the harness half runs in the lint lane — it needs its own
    engine and would double this bench's wall)."""
    from repro.analysis import run_static

    new, stale = run_static()
    for f in new:
        print(f"  reprolint: {f.format()}")
    for e in stale:
        print(f"  reprolint: STALE baseline entry: {e.format()}")
    return not new and not stale


def _request_mix(n: int, prompt_len: int, short_new: int, long_new: int, rng) -> list[tuple[list[int], int]]:
    """75% short / 25% long generations, shuffled so waves mix both."""
    reqs = []
    for i in range(n):
        prompt = rng.integers(1, 256, size=prompt_len).tolist()
        new = long_new if i % 4 == 0 else short_new
        reqs.append((prompt, new))
    rng.shuffle(reqs)
    return reqs


def _run_baseline(engine, requests, slots):
    """Wave-at-a-time serving: every wave decodes to its longest request."""
    t0 = time.perf_counter()
    outs, latencies = [], []
    for w0 in range(0, len(requests), slots):
        wave = requests[w0 : w0 + slots]
        wave_new = max(new for _, new in wave)
        got = engine.generate([p for p, _ in wave], max_new_tokens=wave_new)
        t_wave = time.perf_counter() - t0
        for (_, new), row in zip(wave, got):
            outs.append(row[:new])
            latencies.append(t_wave)  # all submitted at t0; wave finishes together
    wall = time.perf_counter() - t0
    return outs, latencies, wall


def _run_continuous(engine, requests):
    engine.clear_history()
    t0 = time.perf_counter()
    reqs = [engine.submit(p, max_new_tokens=new) for p, new in requests]
    engine.run_until_complete()
    wall = time.perf_counter() - t0
    outs = [r.generated for r in reqs]
    latencies = [r.latency() for r in reqs]
    return outs, latencies, wall, engine.metrics()


def run(quick: bool = False) -> dict:
    banner("serve: paged-KV continuous batching vs slot-granularity baseline")
    cfg = _tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    slots = 4
    n_req = 8 if quick else 48
    prompt_len = 8
    short_new, long_new = (4, 32) if quick else (4, 96)
    max_len = 128
    repeats = 1 if quick else 3
    requests = _request_mix(n_req, prompt_len, short_new, long_new, rng)
    useful = sum(new for _, new in requests)

    baseline = ServeEngine(cfg, params, ServeConfig(slots=slots, max_len=max_len))
    baseline.generate([p for p, _ in requests[:slots]], max_new_tokens=2)  # jit warmup
    continuous = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=slots, max_len=max_len, page_size=8, prefill_chunk=8)
    )
    continuous.generate([p for p, _ in requests[:slots]], max_new_tokens=2)  # jit warmup

    # best-of-N on shared warmed engines: wall-clock on a busy CPU host is
    # noisy and the claim under test is structural, not load-dependent
    b_wall = c_wall = float("inf")
    for _ in range(repeats):
        outs, lat, wall = _run_baseline(baseline, requests, slots)
        if wall < b_wall:
            b_outs, b_lat, b_wall = outs, lat, wall
        outs, lat, wall, metrics = _run_continuous(continuous, requests)
        if wall < c_wall:
            c_outs, c_lat, c_wall, c_metrics = outs, lat, wall, metrics

    # correctness: same tokens from both engines (greedy; prompts replayed
    # identically), plus a B=1/chunk=1 run that is bitwise-bound to the
    # dense-KV reference by construction
    match_all = b_outs == c_outs
    ident_reqs = requests[:3]
    base1 = ServeEngine(cfg, params, ServeConfig(slots=1, max_len=max_len))
    ref = [base1.generate([p], max_new_tokens=new)[0] for p, new in ident_reqs]
    eng1 = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=1, max_len=max_len, page_size=8, prefill_chunk=1)
    )
    got = [eng1.generate([p], max_new_tokens=new)[0] for p, new in ident_reqs]
    bitwise = ref == got

    ring = _run_ring_section(quick)
    prefix = _run_prefix_section(quick)
    tp = _run_tp_section(quick)
    families = _run_families_section(quick)
    sparsity = _run_sparsity_section(quick)
    router = _run_router_section(quick)
    tiering = _run_tiering_section(quick)
    speculative = _run_speculative_section(quick)

    speedup = (useful / c_wall) / (useful / b_wall)
    analysis_clean = _run_analysis_section()
    result = {
        "analysis_clean": analysis_clean,
        "sparsity": sparsity,
        "router": router,
        "tiering": tiering,
        "speculative": speculative,
        "ring": ring,
        "prefix_cache": prefix,
        "tp": tp,
        "families": families,
        "requests": n_req,
        "useful_tokens": useful,
        "baseline": {
            "tok_per_s": useful / b_wall,
            "wall_s": b_wall,
            "p50_latency_s": _pct(sorted(b_lat), 0.50),
            "p99_latency_s": _pct(sorted(b_lat), 0.99),
        },
        "continuous": {
            "tok_per_s": useful / c_wall,
            "wall_s": c_wall,
            "p50_latency_s": _pct(sorted(c_lat), 0.50),
            "p99_latency_s": _pct(sorted(c_lat), 0.99),
            "evictions": c_metrics["evictions"],
        },
        "speedup": speedup,
        "outputs_match_baseline": match_all,
        "bitwise_identical_rho0": bitwise,
    }
    print(
        f"  baseline   : {result['baseline']['tok_per_s']:7.1f} tok/s  "
        f"p50 {result['baseline']['p50_latency_s']:.3f}s p99 {result['baseline']['p99_latency_s']:.3f}s"
    )
    print(
        f"  continuous : {result['continuous']['tok_per_s']:7.1f} tok/s  "
        f"p50 {result['continuous']['p50_latency_s']:.3f}s p99 {result['continuous']['p99_latency_s']:.3f}s"
    )
    print(f"  speedup {speedup:.2f}x | outputs match: {match_all} | bitwise @ rho=0: {bitwise}")
    ring_mb = [(s["max_len"], s["ring_pool_bytes"] / 1e6, s["dense_cache_bytes"] / 1e6) for s in ring["memory_scaling"]]
    print(
        f"  ring       : {ring['tok_per_s']:7.1f} tok/s  bitwise @ rho=0: {ring['bitwise_identical_rho0']} | "
        f"ring pool MB vs dense MB over max_len: "
        + ", ".join(f"{ml}: {r:.2f}/{d:.2f}" for ml, r, d in ring_mb)
    )
    print(
        f"  prefix     : hit rate {prefix['hit_rate']:.2f}, {prefix['pages_shared']} page links shared | "
        f"burst peak pages {prefix['peak_pages_in_use']} vs {prefix['peak_pages_in_use_no_sharing']} unshared | "
        f"tokens identical: {prefix['tokens_identical_to_uncached']} | "
        f"drained: {prefix['allocator_drained_at_shutdown']}"
    )
    print(
        f"  cold burst : {prefix['burst_relinked_pages']} pages relinked mid-wave | "
        f"pages at ready {prefix['burst_pages_at_ready']} vs {prefix['burst_pages_at_ready_no_sharing']} unshared | "
        f"tokens identical: {prefix['burst_tokens_identical']}"
    )
    if "skipped" in tp:
        print(f"  tp         : skipped ({tp['skipped']})")
    else:
        scale_str = ", ".join(
            f"tp={s['tp']}: {s['tok_per_s']:.1f} tok/s {s['pool_bytes_per_shard'] / 1e6:.2f} MB/shard"
            for s in tp["scaling"]
        )
        print(f"  tp         : bitwise {tp['bitwise_identical_tp']} | {scale_str}")
    rw, wh = families["rwkv6"], families["whisper"]
    print(
        f"  rwkv6      : {rw['tok_per_s']:7.1f} tok/s (slot engine {rw['slot_tok_per_s']:.1f}) | "
        f"tokens match dense: {rw['tokens_match_dense']} | "
        f"state bytes flat in max_len: {rw['state_bytes_flat_in_max_len']} "
        f"({rw['state_bytes']['slot'] / 1e3:.1f} kB slot-dense, 0 paged)"
    )
    print(
        f"  whisper    : {wh['tok_per_s']:7.1f} tok/s | tokens match dense: {wh['tokens_match_dense']} | "
        f"drained: {wh['allocator_drained']} | "
        f"cross-KV {wh['state_bytes']['slot'] / 1e3:.1f} kB slot-dense + "
        f"{wh['state_bytes']['paged'] / 1e3:.1f} kB paged self-KV"
    )
    sweep_str = ", ".join(
        f"rho={s['rho']:.2f}: {s['tok_per_s']:.1f} tok/s (live {s['kv_live_frac']:.2f})"
        for s in sparsity["rho_sweep"]
    )
    pv = sparsity["pallas_visits"]
    print(
        f"  sparsity   : skip == mask tokens @ rho={sparsity['parity_rho']}: {sparsity['tile_skip_exact']} | "
        f"{sweep_str} | rho0.5/rho0 {sparsity['rho05_vs_rho0']:.2f}x"
    )
    print(
        f"               pallas pages visited over rho {pv['rhos']}: {pv['pages_visited']} "
        f"(strictly decreasing: {pv['strictly_decreasing']})"
    )
    tht = tiering["host_tier"]
    print(
        f"  tiering    : restore exact {tiering['per_kind_exact']} | "
        f"restore/replay {tiering['restore_vs_replay']:.2f}x on "
        f"{tiering['ratio_workload']['prompt_len']}-token prompts | "
        f"{tht['spills']} spills, {tht['restores']} restores, "
        f"{tht['tier_replays']} tier replays (ratio {tht['restore_ratio']})"
    )
    sp = speculative
    print(
        f"  speculative: k={sp['k']} | streams exact {sp['per_kind_exact']} | "
        f"{sp['accepted_tokens_per_step']:.2f} tokens/dispatch "
        f"(acceptance {sp['acceptance_rate']:.2f}) | "
        f"{sp['tok_per_s']:.1f} tok/s spec vs {sp['tok_per_s_nonspec']:.1f} nonspec "
        f"-> {sp['spec_vs_nonspec']:.2f}x"
    )
    rt = router["ladder"]
    print(
        f"  router     : {router['tok_per_s']:7.1f} tok/s on 2 replicas "
        f"({router['router2_vs_single']:.2f}x vs single) | "
        f"tokens exact: {router['router_tokens_exact']} | drain lossless: {router['router_drain']} | "
        f"affinity hit rate {router['affinity_hit_rate']:.2f}"
    )
    print(
        f"               slo ladder: rho trace {rt['rho_trace']} -> "
        f"{rt['sheds']} sheds from tick {rt['first_shed_tick']} "
        f"(ordered: {router['slo_ladder_ordered']})"
    )
    save("serve_continuous", result)
    if not sparsity["tile_skip_exact"]:
        raise AssertionError("tile-skipped decode diverged from its masked-reference twin")
    if not pv["strictly_decreasing"]:
        raise AssertionError("Pallas page-visit counts did not fall strictly with rho")
    if not quick and sparsity["rho05_vs_rho0"] <= 1.0:
        raise AssertionError(
            f"tile skipping did not pay: rho=0.5 vs rho=0 tokens/s ratio "
            f"{sparsity['rho05_vs_rho0']:.3f} <= 1.0"
        )
    if not bitwise:
        raise AssertionError("paged decode diverged from dense-KV reference at rho=0")
    if not ring["bitwise_identical_rho0"]:
        raise AssertionError("ring-paged decode diverged from dense-KV reference at rho=0")
    if not ring["ring_bytes_flat_in_max_len"]:
        raise AssertionError("ring pool bytes grew with max_len — ring paging is not window-bound")
    if not prefix["tokens_identical_to_uncached"]:
        raise AssertionError("prefix caching changed the emitted tokens")
    if not prefix["hit_rate"] > 0:
        raise AssertionError("shared-system-prompt workload never hit the prefix cache")
    if not prefix["peak_pages_in_use"] < prefix["peak_pages_in_use_no_sharing"]:
        raise AssertionError("prefix sharing did not reduce pages in use")
    if not prefix["allocator_drained_at_shutdown"]:
        raise AssertionError("allocator did not drain to empty after drop_prefix_cache")
    if not prefix["burst_tokens_identical"]:
        raise AssertionError("same-wave dedup changed the emitted tokens")
    if not prefix["burst_relinked_pages"] > 0:
        raise AssertionError("cold same-tick burst never relinked a page mid-wave")
    if not prefix["burst_pages_at_ready"] < prefix["burst_pages_at_ready_no_sharing"]:
        raise AssertionError("same-wave dedup did not reduce pages held at prefill completion")
    if "skipped" not in tp:
        for kind, ok in tp["bitwise_identical_tp"].items():
            if not ok:
                raise AssertionError(f"TP decode diverged from the single-device engine ({kind} pages)")
        for s in tp["scaling"]:
            if not s["shard_bytes_exact"]:
                raise AssertionError(f"tp={s['tp']}: per-shard pool bytes != total/N")
    if not rw["tokens_match_dense"]:
        raise AssertionError("rwkv6 continuous decode diverged from the dense-state replay")
    if not rw["state_bytes_flat_in_max_len"]:
        raise AssertionError("rwkv6 decode-state bytes grew with max_len — slot-dense state is not O(1)/slot")
    if not wh["tokens_match_dense"]:
        raise AssertionError("whisper continuous decode diverged from the dense-state replay")
    if not wh["allocator_drained"]:
        raise AssertionError("whisper allocator did not drain after run_until_complete")
    if not router["router_tokens_exact"]:
        raise AssertionError("2-replica router emitted different tokens than the single engine")
    if not router["router_drain"]:
        raise AssertionError("mid-decode replica kill was not replayed losslessly through the router")
    if not router["slo_ladder_ordered"]:
        raise AssertionError(
            "router shed before saturating the rho ladder — degradation order violated"
        )
    if not router["affinity_hit_rate"] > 0:
        raise AssertionError("warm shared-prefix fleet never scored an affinity hit")
    if not tiering["tier_restore_exact"]:
        raise AssertionError(
            f"host-tier restore diverged from straight decode / evict+replay "
            f"(per-kind: {tiering['per_kind_exact']}, activity: {tiering['per_kind_activity']})"
        )
    if not quick and tiering["restore_vs_replay"] <= 1.0:
        raise AssertionError(
            f"host-tier restore did not beat replay: restore_vs_replay "
            f"{tiering['restore_vs_replay']:.3f} <= 1.0"
        )
    if not speculative["spec_tokens_exact"]:
        raise AssertionError(
            f"speculative decode diverged from the non-speculative engine "
            f"(per-kind: {speculative['per_kind_exact']}, "
            f"evictions: {speculative['per_kind_evictions']})"
        )
    if not quick and speculative["spec_vs_nonspec"] <= 1.0:
        raise AssertionError(
            f"speculation did not pay: spec_vs_nonspec "
            f"{speculative['spec_vs_nonspec']:.3f} <= 1.0"
        )
    if not quick and speedup < 1.5:
        raise AssertionError(f"continuous batching speedup {speedup:.2f}x < 1.5x target")
    return result


if __name__ == "__main__":
    run()
