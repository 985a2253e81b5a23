"""Serving launcher: batched generation with per-request sampling and the
DynaTran runtime accuracy/throughput knob.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --prompts 4 --max-new 16 [--target-rho 0.5] [--temperature 0.8 --top-k 40]

    # token-granularity continuous batching over the paged KV cache, with
    # shared-prefix page caching and token streaming:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --continuous --prompts 16 --max-new 32 --adaptive-rho --stream

    # tensor-parallel serving: shard the paged KV pools + attention over
    # the mesh "model" axis (emulate a mesh on CPU with XLA_FLAGS):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --continuous --tp 4 --prompts 16 --max-new 32

    # speculative decoding: DynaTran-as-draft self-speculation (same weights,
    # sparser thresholds) drafts K tokens per tick; the target verifies all K
    # in one fused dispatch.  Output is bitwise identical to --speculate 0:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --continuous --speculate 3 --draft-rho 0.7 --prompts 8 --max-new 32

    # multi-replica serving: N continuous engines behind the router, with
    # weighted per-tenant fair queuing, SLO-aware rho degradation, and
    # prefix-affinity placement; --metrics dumps the Prometheus text:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --continuous --replicas 2 --prompts 16 --max-new 32 \
        --tenant free:1 --tenant pro:4 --slo-p99-ms 500 --metrics
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.runtime import device_info, enable_compile_cache
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine, ServeConfig, ServeEngine
from repro.serve.sampling import SamplingParams


def _continuous_supported() -> list[str]:
    """Archs the continuous engine serves, derived from the decode-state
    registry (a family is supported iff its module declares a state
    bundle) — never a hand-maintained list."""
    out = []
    for arch in configs.list_archs():
        try:
            zoo.check_serve_support(configs.get_smoke(arch))
            out.append(arch)
        except NotImplementedError:
            pass
    return out


def _synth_inputs(cfg, bundle, rng) -> dict:
    """Synthesize the per-request inputs the state bundle declares (the
    smoke CLI has no real frontend, mirroring the random prompts)."""
    ins = {}
    for name in bundle.required_inputs:
        if name == "frames":
            ins[name] = rng.standard_normal((cfg.encoder_frames, cfg.d_model)).astype(np.float32)
        else:
            raise SystemExit(f"serve CLI cannot synthesize required input '{name}'")
    return ins


def main() -> None:
    supported = _continuous_supported()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--arch", required=True, choices=configs.list_archs(),
        help=f"model architecture (continuous serving covers: {', '.join(supported)})",
    )
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--target-rho", type=float, default=None, help="DynaTran runtime sparsity knob")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0, help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0, help="top-k filter (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0, help="nucleus filter (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0, help="sampling seed (per-request streams are keyed on it)")
    ap.add_argument("--continuous", action="store_true", help="paged-KV continuous batching engine")
    ap.add_argument("--stream", action="store_true", help="[continuous] stream the first request's tokens as they decode")
    ap.add_argument("--slots", type=int, default=8, help="[continuous] decode batch width")
    ap.add_argument("--page-size", type=int, default=16, help="[continuous] tokens per KV page")
    ap.add_argument("--prefill-chunk", type=int, default=16, help="[continuous] prompt tokens per prefill call")
    ap.add_argument("--tp", type=int, default=1, help="[continuous] tensor-parallel shards over the mesh 'model' axis")
    ap.add_argument("--use-pallas", action="store_true",
                    help="[continuous] fused Pallas paged-attention kernel instead of the XLA ref path")
    ap.add_argument(
        "--tile-skip", default=None, choices=["on", "off"],
        help="[continuous] tiled DynaTran datapath: 'on' skips all-dead KV/FFN "
             "tiles, 'off' runs the identical tiled path without skipping "
             "(parity twin); omit for the legacy dense datapath",
    )
    ap.add_argument("--adaptive-rho", action="store_true", help="[continuous] close the rho loop over queue depth")
    ap.add_argument("--replicas", type=int, default=1,
                    help="[continuous] engine replicas behind the multi-replica router")
    ap.add_argument("--tenant", action="append", default=None, metavar="NAME[:WEIGHT]",
                    help="[router] declare a tenant with a fair-share weight (repeatable); "
                         "prompts round-robin over the declared tenants")
    ap.add_argument("--tenant-rate", type=float, default=float("inf"),
                    help="[router] per-tenant token-bucket refill rate (tokens/s; inf = unthrottled)")
    ap.add_argument("--tenant-burst", type=float, default=float("inf"),
                    help="[router] per-tenant token-bucket capacity (tokens)")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="[router] p99 latency SLO; overruns climb the rho ladder before the backlog would")
    ap.add_argument("--metrics", action="store_true",
                    help="[router] print the Prometheus-style metrics text after the run")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="[continuous] speculative decoding: draft K tokens per "
                         "sequence per tick and verify them all in one fused "
                         "dispatch (0 disables; output is bitwise identical "
                         "either way)")
    ap.add_argument("--draft-rho", type=float, default=0.5,
                    help="[speculate] DynaTran sparsity rho for the draft pass "
                         "(self-speculation: same weights, cheaper thresholds; "
                         "runtime knob, never recompiles)")
    ap.add_argument("--draft-arch", default=None, choices=configs.list_archs(),
                    help="[speculate] draft with a separate small model from the "
                         "zoo instead of self-speculation (its paged pools shadow "
                         "the target's page tables)")
    ap.add_argument("--no-prefix-cache", action="store_true", help="[continuous] disable shared-prefix page caching")
    ap.add_argument("--host-tier-mb", type=float, default=64.0,
                    help="[continuous] host page-tier budget (MB): evictions spill KV pages "
                         "to host memory and re-admissions restore them instead of replaying "
                         "prefill; 0 disables the tier (every re-admission replays)")
    ap.add_argument("--kv-cache", default=None, choices=["bfloat16", "int8"], help="KV cache dtype override")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    try:
        zoo.check_serve_support(cfg)
    except NotImplementedError as e:
        raise SystemExit(f"{args.arch}: {e} (supported here: {', '.join(supported)})")
    bundle = zoo.serve_module(cfg).serve_state_bundle(cfg)
    if bundle.required_inputs and not args.continuous:
        raise SystemExit(
            f"{args.arch}: its state bundle needs per-request inputs "
            f"{list(bundle.required_inputs)} — serve it with --continuous"
        )
    if args.kv_cache:
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_cache_dtype=args.kv_cache)
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    sampling = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=args.seed, max_new_tokens=args.max_new,
    )

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=args.prompt_len).tolist() for _ in range(args.prompts)]
    req_inputs = [_synth_inputs(cfg, bundle, rng) for _ in range(args.prompts)]
    dev = device_info()
    print(f"[serve] device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    t0 = time.perf_counter()
    if args.continuous:
        scfg = ContinuousServeConfig(
            slots=min(args.slots, args.prompts),
            max_len=args.max_len,
            page_size=args.page_size,
            prefill_chunk=args.prefill_chunk,
            prefix_caching=not args.no_prefix_cache,
            target_rho=args.target_rho,
            adaptive_rho=args.adaptive_rho,
            tp=args.tp,
            use_pallas=args.use_pallas,
            tile_skip=None if args.tile_skip is None else args.tile_skip == "on",
            host_tier_mb=args.host_tier_mb,
            speculate=args.speculate,
            draft_rho=args.draft_rho,
            draft_arch=args.draft_arch,
        )
        try:
            engines = [ContinuousServeEngine(cfg, params, scfg) for _ in range(max(1, args.replicas))]
        except NotImplementedError as e:  # e.g. --tp on a slot-dense-only family
            raise SystemExit(f"{args.arch}: {e}")
        if args.replicas > 1:
            from repro.router import Router, RouterPolicy, render_prometheus

            weights = {}
            for spec in args.tenant or []:
                name, _, w = spec.partition(":")
                weights[name] = float(w) if w else 1.0
            router = Router(
                engines,
                RouterPolicy(
                    tenant_rate=args.tenant_rate, tenant_burst=args.tenant_burst,
                    slo_p99_ms=args.slo_p99_ms,
                ),
                weights=weights or None,
            )
            tenants = list(weights) or ["default"]
            handles = [
                router.submit(p, tenant=tenants[i % len(tenants)], sampling=sampling, inputs=ins)
                for i, (p, ins) in enumerate(zip(prompts, req_inputs))
            ]
            if args.stream:
                print("[serve] streaming request 0: ", end="", flush=True)
                for tok in handles[0].tokens():
                    print(tok, end=" ", flush=True)
                print()
            router.run_until_complete()
            outs = [h.generated for h in handles]
            dt = time.perf_counter() - t0
            m = router.metrics()
            print(
                f"[serve] router: {m['total_tokens']} tokens over {args.replicas} replicas in {dt:.2f}s "
                f"-> {m['total_tokens'] / dt:.1f} tok/s | completed {m['completed']}/{m['submitted']} "
                f"(sheds {m['sheds']}, throttles {m['throttles']}) | rho {m['rho']:.2f} | "
                f"affinity hit rate {m['affinity_hit_rate']:.2f} | p99 {m['p99_s'] or 0.0:.3f}s"
            )
            if args.metrics:
                print(render_prometheus(m), end="")
            for i, o in enumerate(outs[: min(4, len(outs))]):
                print(f"  out[{i}]: {o[:12]}{'...' if len(o) > 12 else ''}")
            return
        engine = engines[0]
        if args.tp > 1:
            m0 = engine.metrics()
            print(
                f"[serve] tensor-parallel over {engine.mesh}: "
                f"{m0['cache_bytes'] / 1e6:.2f} MB pool, "
                f"{m0['cache_bytes_per_shard'] / 1e6:.2f} MB/shard"
            )
        handles = [engine.submit(p, sampling=sampling, inputs=ins) for p, ins in zip(prompts, req_inputs)]
        if args.stream:
            print("[serve] streaming request 0: ", end="", flush=True)
            for tok in handles[0].tokens():
                print(tok, end=" ", flush=True)
            print()
        engine.run_until_complete()
        outs = [h.generated for h in handles]
        dt = time.perf_counter() - t0
        m = engine.metrics()
        line = (
            f"[serve] continuous: {m['tokens']} tokens in {dt:.2f}s -> {m['tokens']/dt:.1f} tok/s | "
            f"p50 {m['p50_latency_s']:.3f}s p99 {m['p99_latency_s']:.3f}s | "
            f"evictions {m['evictions']} rho {m['rho']:.2f}"
        )
        if m["prefix_cache"] is not None:
            pc = m["prefix_cache"]
            line += f" | prefix hit rate {pc['hit_rate']:.2f} ({pc['pages_shared']} page links shared)"
        if m["host_tier"] is not None:
            ht = m["host_tier"]
            line += f" | tier spills {ht['spills']} restores {ht['restores']} replays {ht['tier_replays']}"
        if m["speculative"] is not None:
            sp = m["speculative"]
            rate = sp["acceptance_rate"]
            line += (
                f" | spec k={sp['k']} ({sp['mode']}) accepted {sp['accepted']}/{sp['drafted']}"
                + (f" ({rate:.2f})" if rate is not None else "")
            )
        print(line)
    else:
        engine = ServeEngine(cfg, params, ServeConfig(slots=args.prompts, max_len=args.max_len, target_rho=args.target_rho))
        outs = engine.generate(prompts, sampling=sampling)
        dt = time.perf_counter() - t0
        toks = sum(len(o) for o in outs)
        print(f"[serve] {args.prompts} prompts x {args.max_new} new tokens in {dt:.2f}s -> {toks/dt:.1f} tok/s")
    for i, o in enumerate(outs[: min(4, len(outs))]):
        print(f"  out[{i}]: {o[:12]}{'...' if len(o) > 12 else ''}")


if __name__ == "__main__":
    main()
