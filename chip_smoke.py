#!/usr/bin/env python3
"""Smoke run of the serve path on a TPU: qwen3-4b at its full published
width (36 layers, d_model 2560, 32/8 heads, head_dim 128, vocab 151936,
random bf16 weights from ``--seed``) served through ``ContinuousServeEngine``.

    python chip_smoke.py             # one chip: ref and Pallas datapaths
    python chip_smoke.py --chips 4   # the TP=4 engine against the TP=1 engine

One chip, in one process:

1. device gate: print the platform, device kind and count; no TPU -> exit 1;
2. serve 8 seeded requests (128-256 prompt tokens, 32 new tokens, greedy and
   sampled) on the default XLA ref datapath, twice (compile, then warm);
3. the same on the fused Pallas datapath (``use_pallas=True``), checking that
   the kernels are compiled (``interpret=False``, ``tpu_custom_call`` in the
   compiled decode step);
4. correctness: paged prefill + decode logits against ``zoo.forward`` on the
   same tokens for both datapaths, and ``paged_decode_attention`` against the
   ref decode attention, each within the relative tolerance stated below.

``--chips 4`` runs only the tensor-parallel comparison: the same requests
through a TP=4 engine (KV heads sharded over a (1, 4) mesh) and a TP=1
engine, reporting token agreement, the logit error between the two, the
mesh's devices and the pool bytes held on each device.

Any failed check or phase raises, so the exit code is non-zero.  On success
the last line of stdout is ``{"ok": true, "device": {...}}``; nothing else
prints it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

# relative tolerances: max |got - ref| / max |ref|.  Logits differ by bf16
# rounding of activations along two op orders through 36 layers; the kernel
# differs from the ref attention by bf16 rounding of its output.
LOGIT_RTOL = 5e-2
KERNEL_RTOL = 1e-2
ARCH = "qwen3-4b"


@dataclasses.dataclass(frozen=True)
class Shape:
    """The served shape: engine capacity and the request mix."""

    slots: int = 8
    max_len: int = 1024
    page_size: int = 16
    requests: int = 8
    prompt_len: tuple[int, int] = (128, 256)
    max_new: int = 32
    check_decode: int = 8  # decode steps in the logit check


def _import_repo() -> None:
    """Put this checkout's ``src/`` first on the path; the script is part of
    the repo and never runs against another copy of the program."""
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "serve" / "engine.py").is_file():
        raise SystemExit(f"chip_smoke.py: no program next to the script (missing {src / 'repro'})")
    sys.path.insert(0, str(src))


def device_gate(min_count: int = 1) -> dict:
    """Phase 1: the device JAX sees.  Anything but a TPU ends the run."""
    from repro.launch.runtime import device_info

    dev = device_info()
    print(f"[smoke] device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke.py: needs a TPU, JAX sees platform {dev['platform']!r}")
    if dev["count"] < min_count:
        raise SystemExit(f"chip_smoke.py: needs {min_count} chips, JAX sees {dev['count']}")
    return dev


class CompileMeter:
    """Seconds JAX spends compiling (or loading from the persistent cache),
    and the persistent cache's hits and misses, from JAX's own events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses


def make_requests(cfg, n: int, prompt_len: tuple[int, int], max_new: int, seed: int) -> list:
    """``n`` seeded (prompt, SamplingParams) pairs; even rows greedy, odd
    rows sampled."""
    import numpy as np

    from repro.serve.sampling import SamplingParams

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(1, cfg.vocab, size=int(rng.integers(prompt_len[0], prompt_len[1] + 1))).tolist()
        sp = (SamplingParams(max_new_tokens=max_new) if i % 2 == 0
              else SamplingParams(temperature=0.8, top_k=50, top_p=0.95, seed=seed + i, max_new_tokens=max_new))
        out.append((prompt, sp))
    return out


def serve(engine, requests: list) -> dict:
    """Submit every request, run the engine dry, and check that each one
    completed with exactly its token budget."""
    t0 = time.perf_counter()
    handles = [engine.submit(prompt, sampling=sp) for prompt, sp in requests]
    engine.run_until_complete()
    wall = time.perf_counter() - t0
    for i, (h, (_, sp)) in enumerate(zip(handles, requests)):
        if not h.done or h.cancelled or h.shed or len(h.generated) != sp.max_new_tokens:
            raise AssertionError(
                f"request {i}: done={h.done} cancelled={h.cancelled} shed={h.shed} "
                f"generated {len(h.generated)} of {sp.max_new_tokens} tokens"
            )
    tokens = sum(len(h.generated) for h in handles)
    return {"outputs": [list(h.generated) for h in handles], "tokens": tokens, "wall_s": wall}


def serve_phase(name: str, cfg, params, scfg, request_sets: list, meter: CompileMeter | None = None):
    """Build one engine and serve each request set through it in turn (the
    first set pays for compilation).  Returns the engine and a report."""
    from repro.serve.engine import ContinuousServeEngine

    engine = ContinuousServeEngine(cfg, params, scfg)
    runs = []
    for i, reqs in enumerate(request_sets):
        before = meter.snapshot() if meter else None
        run = serve(engine, reqs)
        if meter:
            after = meter.snapshot()
            run["compile_s"] = after[0] - before[0]
            run["cache_hits"], run["cache_misses"] = after[1] - before[1], after[2] - before[2]
        runs.append(run)
        extra = (f" compile {run['compile_s']:.2f}s (cache hits {run['cache_hits']}, misses {run['cache_misses']})"
                 if meter else "")
        print(f"[smoke] {name} pass {i}: {len(reqs)} requests, {run['tokens']} tokens in {run['wall_s']:.2f}s "
              f"-> {run['tokens'] / run['wall_s']:.1f} tok/s{extra}", flush=True)
    return engine, runs


def decode_step_hlo(engine) -> str:
    """Compiled HLO of the engine's jitted decode step, at the engine's own
    argument shapes (lowering never runs or donates anything)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.sampling import sampling_tensors

    b = engine.scfg.slots
    st = sampling_tensors(b)
    lowered = engine._decode.lower(
        engine.params, engine.pools, engine.slot_state, engine.occupancy, engine._tables_for([]),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), bool), engine.policy,
        st["temps"], st["top_ks"], st["top_ps"], st["seeds"], jnp.asarray(np.zeros((b,), np.int32)),
        sample=False,
    )
    return lowered.compile().as_text()


def check_compiled_kernels(engine) -> None:
    """Phase 3's datapath checks: the Pallas kernels run compiled."""
    if engine.policy.interpret is not False:
        raise AssertionError(f"engine policy runs Pallas interpreted (interpret={engine.policy.interpret})")
    if "tpu_custom_call" not in decode_step_hlo(engine):
        raise AssertionError("compiled decode step holds no tpu_custom_call: the Pallas kernel is not in it")
    print("[smoke] pallas datapath: policy interpret=False, tpu_custom_call in the compiled decode step",
          flush=True)


def paged_logits(engine, tokens: list[int], n_prompt: int):
    """Logits of the engine's own paged steps for one sequence, as row 0
    over pages 1.. of the engine's pools: ``tokens[:n_prompt]`` through
    chunked prefill, then one decode step per remaining token.  The pools
    are taken from the engine, which serves nothing after this.  Returns
    [len(tokens) - n_prompt + 1, vocab] f32, the logits for positions
    n_prompt - 1 .. len(tokens) - 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, chunk, policy = engine.cfg, engine.scfg.prefill_chunk, engine.policy
    tables = {k: jnp.arange(1, n + 1, dtype=jnp.int32)[None] for k, n in engine.budgets.items()}
    # the smoke model (dense attention) keeps no slot-dense state: ssm=None
    prefill = jax.jit(lambda p, pools, occ, s, t, nv: engine._step_prefill(
        p, pools, None, occ, tables, s, t, nv, s == 0, policy), donate_argnums=(1, 2))
    decode = jax.jit(lambda p, pools, occ, n, t: engine._step_decode(
        p, pools, None, occ, tables, n, t, jnp.ones((1,), bool), policy), donate_argnums=(1, 2))
    pools, occ = engine.pools, engine.occupancy
    engine.pools = engine.occupancy = None
    rows = []
    for s in range(0, n_prompt, chunk):
        piece = tokens[s:min(s + chunk, n_prompt)]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, : len(piece)] = piece
        logits, pools, occ, _ = prefill(engine.params, pools, occ, jnp.asarray([s], jnp.int32),
                                        jnp.asarray(toks), jnp.asarray([len(piece)], jnp.int32))
    rows.append(logits.reshape(-1)[: cfg.vocab])
    for t in range(n_prompt, len(tokens)):
        logits, pools, occ, _ = decode(engine.params, pools, occ, jnp.asarray([t], jnp.int32),
                                       jnp.asarray([[tokens[t]]], jnp.int32))
        rows.append(logits.reshape(-1)[: cfg.vocab])
    return jnp.stack(rows).astype(jnp.float32)


def rel_err(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def check_logits(name: str, engine, tokens: list[int], n_prompt: int, forward_logits) -> float:
    """Phase 4, model level: the engine's paged path against ``zoo.forward``."""
    got = paged_logits(engine, tokens, n_prompt)
    err = rel_err(got, forward_logits)
    print(f"[smoke] {name} logits vs zoo.forward: max rel err {err:.3e} (tol {LOGIT_RTOL})", flush=True)
    if not err <= LOGIT_RTOL:
        raise AssertionError(f"{name}: paged logits differ from zoo.forward by {err:.3e} > {LOGIT_RTOL}")
    return err


def forward_logits(cfg, params, tokens: list[int], n_prompt: int):
    """``zoo.forward`` logits at positions n_prompt - 1 .. len(tokens) - 1."""
    import jax
    import jax.numpy as jnp

    from repro.models import zoo

    logits = jax.jit(lambda p, t: zoo.forward(p, cfg, t)[0])(params, jnp.asarray([tokens], jnp.int32))
    return logits[0, n_prompt - 1:, : cfg.vocab].astype(jnp.float32)


def check_kernel(cfg, max_len: int, page_size: int, batch: int, seed: int, interpret: bool | None = None) -> float:
    """Phase 4, kernel level: ``paged_decode_attention`` against the ref
    decode attention over gathered pages, at the config's head widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_decode_attention
    from repro.models.attention import decode_attention
    from repro.models.kvcache import gather_pages

    maxp = max_len // page_size
    n_pages = batch * maxp + 1
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool_shape = (n_pages, page_size, cfg.kv_heads, cfg.hd)
    k_pool = jax.random.normal(ks[0], pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], pool_shape, jnp.bfloat16)
    q = jax.random.normal(ks[2], (batch, 1, cfg.heads, cfg.hd), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[: batch * maxp].reshape(batch, maxp), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, maxp * page_size + 1, size=batch), jnp.int32)
    got = paged_decode_attention(q, k_pool, v_pool, table, lengths, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = decode_attention(q.astype(jnp.float32), gather_pages(k_pool, table), gather_pages(v_pool, table),
                               lengths)
    err = rel_err(got, ref)
    print(f"[smoke] paged_decode_attention vs ref decode attention (B={batch} H={cfg.heads} Hkv={cfg.kv_heads} "
          f"D={cfg.hd} P={page_size} max_len={max_len}): max rel err {err:.3e} (tol {KERNEL_RTOL})", flush=True)
    if not err <= KERNEL_RTOL:
        raise AssertionError(f"paged_decode_attention differs from the ref attention by {err:.3e} > {KERNEL_RTOL}")
    return err


def greedy_agreement(requests: list, a: list, b: list) -> tuple[int, int]:
    """(agreeing, total) greedy requests between two runs' outputs."""
    idx = [i for i, (_, sp) in enumerate(requests) if sp.temperature <= 0]
    return sum(a[i] == b[i] for i in idx), len(idx)


def peak_hbm() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(cfg, params, shape: Shape, seed: int, meter: CompileMeter) -> None:
    """Phases 2-4 on one chip."""
    from repro.serve.engine import ContinuousServeConfig

    check_kernel(cfg, shape.max_len, shape.page_size, shape.slots, seed)
    sets = [make_requests(cfg, shape.requests, shape.prompt_len, shape.max_new, seed + k) for k in range(2)]
    scfg = ContinuousServeConfig(slots=shape.slots, max_len=shape.max_len, page_size=shape.page_size)
    # the correctness sequence: one prompt plus its greedy continuation
    prompt, _ = sets[0][0]
    n_prompt = len(prompt)

    ref_engine, ref_runs = serve_phase("ref", cfg, params, scfg, sets, meter)
    tokens = prompt + ref_runs[0]["outputs"][0][: shape.check_decode]
    fwd = forward_logits(cfg, params, tokens, n_prompt)
    check_logits("ref", ref_engine, tokens, n_prompt, fwd)
    del ref_engine
    gc.collect()

    pal_engine, pal_runs = serve_phase("pallas", cfg, params, dataclasses.replace(scfg, use_pallas=True), sets, meter)
    check_compiled_kernels(pal_engine)
    check_logits("pallas", pal_engine, tokens, n_prompt, fwd)
    del pal_engine
    gc.collect()

    for k, reqs in enumerate(sets):
        agree, total = greedy_agreement(reqs, ref_runs[k]["outputs"], pal_runs[k]["outputs"])
        print(f"[smoke] pass {k}: greedy tokens agree between ref and pallas in {agree}/{total} requests",
              flush=True)


def device_bytes(tree) -> dict[str, int]:
    """Bytes of ``tree`` held on each device."""
    import jax

    out: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[str(shard.device)] = out.get(str(shard.device), 0) + shard.data.nbytes
    return out


def run_four_chips(cfg, params, shape: Shape, seed: int) -> None:
    """TP=4 against TP=1 on the same requests, and nothing else.  Both
    engines get the same single-device weights; the TP=4 engine places
    them on its mesh itself."""
    import jax

    from repro.serve.engine import ContinuousServeConfig

    reqs = make_requests(cfg, shape.requests, shape.prompt_len, shape.max_new, seed)
    scfg = ContinuousServeConfig(slots=shape.slots, max_len=shape.max_len, page_size=shape.page_size)
    prompt, _ = reqs[0]
    n_prompt = len(prompt)

    tp1, (run1,) = serve_phase("tp=1", cfg, params, scfg, [reqs])
    tokens = prompt + run1["outputs"][0][: shape.check_decode]
    logits1 = paged_logits(tp1, tokens, n_prompt)
    del tp1
    gc.collect()

    tp4, (run4,) = serve_phase("tp=4", cfg, params, dataclasses.replace(scfg, tp=4), [reqs])
    print(f"[smoke] tp=4 mesh: {tp4.mesh.shape} over {[str(d) for d in tp4.mesh.devices.flat]}", flush=True)
    total = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tp4.pools))
    print(f"[smoke] tp=4 pool bytes: total {total}, per device {device_bytes(tp4.pools)}", flush=True)
    print(f"[smoke] tp=4 param bytes per device {device_bytes(tp4.params)}", flush=True)
    logits4 = paged_logits(tp4, tokens, n_prompt)
    err = rel_err(logits4, logits1)
    agree = sum(a == b for a, b in zip(run1["outputs"], run4["outputs"]))
    print(f"[smoke] tp=4 vs tp=1: tokens agree in {agree}/{len(reqs)} requests; "
          f"logits max rel err {err:.3e} (tol {LOGIT_RTOL})", flush=True)
    if not err <= LOGIT_RTOL:
        raise AssertionError(f"tp=4 logits differ from tp=1 by {err:.3e} > {LOGIT_RTOL}")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: both datapaths on one chip; 4: the TP=4 engine against TP=1 only")
    ap.add_argument("--seed", type=int, default=0, help="weights, prompts and sampling seed")
    args = ap.parse_args(argv)

    _import_repo()
    dev = device_gate(args.chips)
    from repro.launch.runtime import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter()

    import jax

    from repro import configs
    from repro.models import zoo

    cfg = configs.get_config(ARCH)
    t0 = time.perf_counter()
    params = zoo.init_params(jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"[smoke] {ARCH}: {cfg.layers} layers d_model {cfg.d_model} heads {cfg.heads}/{cfg.kv_heads} "
          f"head_dim {cfg.hd} vocab {cfg.vocab}; {n_bytes} param bytes initialised in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)

    if args.chips == 4:
        run_four_chips(cfg, params, Shape(), args.seed)
    else:
        run_one_chip(cfg, params, Shape(), args.seed, meter)
    secs, hits, misses = meter.snapshot()
    print(f"[smoke] compile total {secs:.2f}s (persistent cache hits {hits}, misses {misses}); "
          f"peak HBM bytes in use {peak_hbm()}", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev["platform"], "kind": dev["kind"],
                                             "count": dev["count"]}}))


if __name__ == "__main__":
    main()
