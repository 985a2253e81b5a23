"""Serving example: the DynaTran runtime knob and the request-lifecycle
API, three ways.

1. Fixed knob on the slot-granularity baseline — trade accuracy for
   throughput at serve time without recompilation (paper Fig. 19).
2. Closed loop on the paged-KV continuous-batching engine — a burst of
   requests deepens the queue, the RhoController raises target_rho along
   the profiled transfer curves, and rho relaxes back once the burst
   drains.
3. Request lifecycle — per-request SamplingParams (temperature / top-k /
   top-p / seed enter the jitted step as runtime per-row scalars), token
   streaming + cancellation, and refcounted shared-prefix page caching
   (requests with the same system prompt link the same physical KV pages,
   copy-on-write).
4. Tile skipping under load — the tiled DynaTran datapath
   (``tile_skip=True``): the RhoController deepens target_rho with the
   queue, each tick re-resolves the KernelPolicy taus from the profiled
   transfer curves (runtime pytree leaves — the knob never recompiles),
   scatter-time occupancy bits go dead, and the skipping kernels read
   fewer KV pages per token.  Watch occupancy fall and tokens/s rise as
   the burst deepens.

    PYTHONPATH=src python examples/serve_dynamic.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.core.dynatran import SparsityConfig, ThresholdCalculator, TransferCurve
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine, ServeConfig, ServeEngine
from repro.serve.sampling import SamplingParams


def kv_live_share(engine) -> float:
    """Share of the pool's KV positions whose occupancy bit is live
    (unwritten pages start all-live, so it tightens as the pool fills)."""
    bits = [np.asarray(v) for v in jax.tree_util.tree_leaves(engine.occupancy)]
    return sum(float(b.sum()) for b in bits) / max(sum(b.size for b in bits), 1)


def fixed_knob_baseline(cfg, params, prompts):
    for rho in (None, 0.3, 0.6):
        engine = ServeEngine(cfg, params, ServeConfig(slots=4, max_len=128, target_rho=rho))
        t0 = time.perf_counter()
        outs = engine.generate(prompts, max_new_tokens=16)
        dt_s = time.perf_counter() - t0
        label = "dense-profile" if rho is None else f"rho={rho}"
        print(f"[serve] {label:14s}: {sum(len(o) for o in outs)/dt_s:7.1f} tok/s, first out {outs[0][:6]}")


def adaptive_rho_burst(cfg, params, prompts):
    engine = ContinuousServeEngine(
        cfg,
        params,
        ContinuousServeConfig(
            slots=4, max_len=128, page_size=16, prefill_chunk=8,
            adaptive_rho=True, rho_max=0.6, depth_lo=1, depth_hi=8,
        ),
    )
    for p in prompts * 4:  # burst: queue depth >> slots
        engine.submit(p, max_new_tokens=12)
    trace = []
    while engine.sched.queue or engine.sched.active:
        engine.step()
        trace.append((engine.sched.queue_depth, engine.current_rho))
    m = engine.metrics()
    peak = max(r for _, r in trace)
    print(
        f"[serve] continuous burst: {m['tokens']} tokens, p50 {m['p50_latency_s']:.3f}s "
        f"p99 {m['p99_latency_s']:.3f}s | rho peaked at {peak:.2f} under load, "
        f"relaxed to {trace[-1][1]:.2f} when drained"
    )


def request_lifecycle(cfg, params):
    """Streaming, cancellation, per-request sampling, shared prefixes."""
    rng = np.random.default_rng(7)
    system = rng.integers(1, cfg.vocab, size=16).tolist()  # shared "system prompt"
    engine = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=4, max_len=128, page_size=8, prefill_chunk=8)
    )
    # warm the prefix cache, then fan out same-prefix requests with
    # DIFFERENT per-request sampling policies in one decode batch
    engine.generate([system + rng.integers(1, cfg.vocab, size=4).tolist()], max_new_tokens=8)
    handles = [
        engine.submit(
            system + rng.integers(1, cfg.vocab, size=4).tolist(),
            sampling=SamplingParams(temperature=t, top_k=40, seed=i, max_new_tokens=12),
        )
        for i, t in enumerate((0.0, 0.7, 1.0, 1.3))
    ]
    victim = engine.submit(system + rng.integers(1, cfg.vocab, size=4).tolist(), max_new_tokens=12)

    stream = []
    for tok in handles[1].tokens():  # drives engine.step() under the hood
        stream.append(tok)
        if len(stream) == 4:
            victim.cancel()  # frees its slot + page links immediately
    engine.run_until_complete()
    m = engine.metrics()
    pc = m["prefix_cache"]
    print(f"[serve] streamed 12 tokens from a temperature=0.7 request: {stream[:6]}...")
    print(
        f"[serve] lifecycle: greedy row {handles[0].generated[:4]}, hot row {handles[3].generated[:4]} "
        f"decoded in ONE batch; cancelled request freed after {len(victim.generated)} tokens"
    )
    print(
        f"[serve] prefix cache: hit rate {pc['hit_rate']:.2f}, {pc['pages_shared']} page links shared, "
        f"burst peak {m['peak_pages_in_use']} pages in use"
    )


def tile_skip_under_load(cfg, params):
    """The closed rho loop driving the TILED datapath: occupancy bits are
    marked at scatter time from the tick's tau_kv, and the skipping kernels
    drop all-dead pages — so a deeper queue buys throughput, not just
    cheaper activations."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab, size=48).tolist() for _ in range(12)]

    # profile the "kv" transfer curve off a short legacy-datapath run: tau at
    # rho r is the r-quantile of the cached per-position max|k|, so the
    # controller's rho maps onto a real dead fraction of the cache
    probe = ContinuousServeEngine(
        cfg, params, ContinuousServeConfig(slots=1, max_len=128, page_size=4, prefill_chunk=8)
    )
    probe.generate(prompts[:1], max_new_tokens=4)
    mags = np.concatenate([
        np.abs(np.asarray(leaf)).max(axis=(-2, -1)).ravel()
        for leaf in jax.tree_util.tree_leaves(probe.pools.k)
    ])
    rhos = np.linspace(0.0, 1.0, 9)
    taus_kv = np.quantile(mags[mags > 0], rhos)  # unwritten slots are zero
    taus_kv[0] = 0.0
    calc = ThresholdCalculator({
        "kv": TransferCurve(taus=jnp.asarray(taus_kv, jnp.float32), rhos=jnp.asarray(rhos, jnp.float32)),
        "ffn_act": TransferCurve(taus=jnp.linspace(0.0, 0.2, 9), rhos=jnp.asarray(rhos, jnp.float32)),
        "attn_out": TransferCurve(taus=jnp.linspace(0.0, 0.05, 9), rhos=jnp.asarray(rhos, jnp.float32)),
    })

    engine = ContinuousServeEngine(
        cfg, params,
        ContinuousServeConfig(slots=2, max_len=128, page_size=4, prefill_chunk=8,
                              adaptive_rho=True, rho_max=0.75, depth_lo=1, depth_hi=8,
                              tile_skip=True),
        calculator=calc,
    )
    for p in prompts:
        engine.submit(p, max_new_tokens=12)
    print(f"[serve] tile-skip burst: {len(prompts)} requests over 2 slots, rho_max 0.75")
    tick, last_toks, last_t = 0, 0, time.perf_counter()
    while engine.sched.queue or engine.sched.active:
        engine.step()
        tick += 1
        if tick % 10 == 0 or not (engine.sched.queue or engine.sched.active):
            m = engine.metrics()
            now = time.perf_counter()
            rate = (m["tokens"] - last_toks) / max(now - last_t, 1e-9)
            last_toks, last_t = m["tokens"], now
            print(
                f"  tick {tick:3d}: queue {m['queue_depth']:2d} | rho {m['rho']:.2f} "
                f"-> tau_kv {np.interp(m['rho'], rhos, taus_kv):.2f} | "
                f"kv occupancy live {kv_live_share(engine):.2f} | {rate:7.1f} tok/s"
            )
    m = engine.metrics()
    print(
        f"[serve] tile-skip burst done: {m['tokens']} tokens, p50 {m['p50_latency_s']:.3f}s "
        f"p99 {m['p99_latency_s']:.3f}s | final kv occupancy {kv_live_share(engine):.2f}"
    )


def main():
    cfg = get_smoke("gemma2-9b")  # reduced gemma-2 family config (CPU-sized)
    cfg = dataclasses.replace(cfg, sparsity=SparsityConfig(mode="dynatran", target_rho=0.3))
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=12).tolist() for _ in range(4)]

    fixed_knob_baseline(cfg, params, prompts)

    # the paged engine pages sliding-window layers into ring tables, so the
    # continuous demo runs the gemma-2 reduction itself: the "sliding" half
    # of its local/global stack costs ceil(window/P)+1 pages per sequence
    ccfg = dataclasses.replace(cfg, sparsity=SparsityConfig(mode="dynatran", target_rho=0.0))
    adaptive_rho_burst(ccfg, params, prompts)

    # prefix sharing needs an all-full-attention layout (ring pages are
    # per-sequence), so the lifecycle demo runs a dense config
    dense = dataclasses.replace(
        get_smoke("qwen3-4b"), sparsity=SparsityConfig(mode="none", target_rho=0.0)
    )
    dense_params = zoo.init_params(jax.random.PRNGKey(1), dense)
    request_lifecycle(dense, dense_params)

    # the tiled datapath needs the "kv" site opted in (occupancy bits are
    # only written for sites the policy wants)
    sparse = dataclasses.replace(
        dense, sparsity=SparsityConfig(mode="dynatran", sites=("ffn_act", "attn_out", "kv"))
    )
    tile_skip_under_load(sparse, dense_params)


if __name__ == "__main__":
    main()
