"""Run one cell of ``BENCHMARK.json`` on the accelerator this process holds.

    python -m bench.run --workload qwen3-4b.chat --seed 7 --seconds 40 --trace 0

Prints the device first, then progress on stderr.  The last lines of
stderr give each number the correctness check compared, beside its limit;
the last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

from bench import registry  # noqa: E402

DRAIN_LIMIT_S = 60.0  # how long past the close a request sent in the window is followed
SAMPLE_TOKENS = 512  # served tokens the correctness sample aims for
SAMPLE_REQUESTS = 8  # at most this many requests in it


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """Compiles (or persistent-cache loads) and cache hits, from JAX's own events."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        self.names: list[str] = []

        def on_duration(event, duration, fun_name=None, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += duration
                self.names.append(str(fun_name))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds, "hits": self.hits, "misses": self.misses}


def device_gate(chips: int, require_chip: bool) -> dict:
    """The device JAX sees, printed first.  Anything but a TPU, or fewer
    chips than the cell asks for, ends the run with no result."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"[bench] device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}", flush=True)
    if require_chip and dev["platform"] != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX sees platform {dev['platform']!r}")
    if require_chip and dev["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees {dev['count']}")
    return dev


def pick_sample(records, window, seed: int) -> list:
    """Greedy requests finished since the window opened: the longest, then
    others drawn from the seed, those of engine slots not yet in the sample
    first, until the sample holds ``SAMPLE_TOKENS`` served tokens or
    ``SAMPLE_REQUESTS`` requests."""
    import numpy as np

    done = [r for r in records if r.sent.greedy and r.done_t is not None and r.done_t >= window[0]]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.sent.prompt) + len(r.req.generated), r.send_t))
    sample, rest = [done[-1]], done[:-1]
    rest = [rest[i] for i in np.random.default_rng(int(seed) + 2).permutation(len(rest))]
    seen, fresh = {done[-1].slot}, []
    for r in rest:
        if r.slot not in seen:
            seen.add(r.slot)
            fresh.append(r)
    rest = fresh + [r for r in rest if all(r is not f for f in fresh)]
    tokens = len(done[-1].req.generated)
    for r in rest:
        if tokens >= SAMPLE_TOKENS or len(sample) >= SAMPLE_REQUESTS:
            break
        sample.append(r)
        tokens += len(r.req.generated)
    return sample


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, engine_hook=None, control: bool = False) -> dict:
    """One run of cell ``name``; returns the result object.  Tests pass
    ``require_chip=False`` (no TPU gate, no persistent compile cache) and
    may pass ``engine_hook`` to alter the engine before it serves;
    ``control`` puts the float8 control in the program's place: the check
    judges the gaps of the tokens it puts first on the same sample, and
    the result keeps the program's own gaps beside them."""
    bench = registry.load_benchmark(root)
    cell = registry.workload(bench, name)
    cfg_file = registry.config_file(root, cell["config"])
    mix = registry.mix_file(root, cell["traffic"])
    checks = json.loads((registry.bench_dir(root) / "checks" / f"{name}.json").read_text())
    e2e, per_layer = registry.cell_metrics(bench, name)
    dep = cfg_file["deployment"]

    from bench.serve import import_program

    import_program(root)
    dev = device_gate(int(cell["chips"]), require_chip)

    import jax

    from bench import serve
    from bench.model import spec_from_file
    from bench.stats import Ctx
    from bench.traffic import Traffic
    from repro.launch.runtime import enable_compile_cache

    if require_chip:
        log(f"compile cache: {enable_compile_cache()}")
        # every program, however quick to compile, is kept: only a cell's first run compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    meter = CompileMeter()
    try:
        peak = registry.peaks(root, dev["kind"])
    except KeyError:
        if require_chip:
            raise
        peak = None

    spec = spec_from_file(cfg_file)
    mc = serve.model_config(cfg_file)
    rows = serve.vocab_rows(mc)
    ref = registry.family_module(root, "reference", spec.family)
    work = registry.family_module(root, "work", spec.family)
    t = time.perf_counter()
    weights = ref.make_weights(spec, seed, rows)
    engine = serve.build_engine(mc, serve.program_params(weights, mc), dep)
    del weights
    jax.block_until_ready(engine.params)
    log(f"{spec.name}: {spec.layers} layers d_model {spec.d_model} heads {spec.heads}/{spec.kv_heads} "
        f"head_dim {spec.head_dim} d_ff {spec.d_ff} vocab {spec.vocab}; weights and engine in "
        f"{time.perf_counter() - t:.2f}s")
    if engine_hook is not None:
        engine_hook(engine)

    traffic = Traffic(mix, spec.vocab, int(dep["slots"]), seed)
    traffic.check_fits(int(dep["max_len"]), int(dep["num_pages"]), engine.scfg.page_size)
    t = time.perf_counter()
    serve.warm_up(engine, spec.vocab, seed)
    log(f"warm-up {time.perf_counter() - t:.2f}s: {meter.snapshot()}")
    fillers = traffic.fillers(int(dep["slots"]), int(dep["num_pages"]), engine.scfg.page_size,
                              int(dep["max_len"]))
    for what, prompts in (("pool fill", fillers),
                          ("documents", traffic.documents)):
        if prompts:
            t = time.perf_counter()
            serve.prefill_prompts(engine, prompts)
            log(f"{what}: {len(prompts)} prompts, {sum(map(len, prompts))} tokens, "
                f"cached in {time.perf_counter() - t:.2f}s")
    loop = serve.ClosedLoop(engine, traffic)
    lead_in = traffic.clients * traffic.stagger_ticks + traffic.lead_in_ticks
    t = time.perf_counter()
    while loop.tick_no < lead_in:
        loop.tick()
    log(f"lead-in {lead_in} ticks {time.perf_counter() - t:.2f}s")
    before = meter.snapshot()
    n_names = len(meter.names)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.2f}s: {before}")

    trace_dir = None
    if trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
        jax.profiler.start_trace(str(trace_dir))
        with jax.profiler.TraceAnnotation("bench.window"):
            window = loop.run_for(seconds)
        jax.profiler.stop_trace()
    else:
        window = loop.run_for(seconds)
    after = meter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    log(f"window {window[1] - window[0]:.3f}s, {loop.tick_no - lead_in} ticks; "
        f"compiled in the window: {in_window} {meter.names[n_names:]}")
    loop.drain(window, DRAIN_LIMIT_S)
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    ctx = Ctx(records=loop.records, ticks=loop.ticks, window=window, setup_s=setup_s,
              spec=spec, work=work, peak=peak)
    breakdown = traced = None
    if trace_dir is not None:
        from bench import trace as trace_mod

        t = time.perf_counter()
        ctx.trace = trace_mod.reduce_file(trace_mod.find_xplane(trace_dir))
        ctx.trace_window = window
        shutil.rmtree(trace_dir, ignore_errors=True)
        traced = ctx.trace
        breakdown = {"device_ops": traced["ops"][:10], "idle_gaps": traced["idle_gaps"][:10]}
        log(f"trace reduced in {time.perf_counter() - t:.2f}s: busy {ctx.trace['busy_s']:.4f}s of "
            f"{ctx.trace['window_s']:.4f}s; modules {ctx.trace['modules']}")
    metrics = {}
    for m in (per_layer if trace else e2e):
        got = registry.metric_reader(root, m["name"]).read(ctx)
        if isinstance(got, dict):
            log(f"{m['name']}: {got['note']}")
            got = got["value"]
        if got is not None:
            metrics[m["name"]] = {"value": float(got), "unit": m["unit"]}
    sent = ctx.sent_in_window()
    attempted = len(sent)
    failed = sum(1 for r in sent if r.first_t is None)
    finished = sum(1 for r in loop.records if r.done_t is not None and window[0] <= r.done_t <= window[1])
    log(f"window: {attempted} sent, {finished} finished, {ctx.tokens_in_window()} tokens, "
        f"{len(ctx.gaps_s())} gaps; peak device memory {memory_peak}")

    # correctness: free the program's state, then the reference over the sample
    sample = [(list(r.sent.prompt), list(r.req.generated)) for r in pick_sample(loop.records, window, seed)]
    serve.release(engine)
    del loop, engine, ctx, sent
    gc.collect()
    log(f"device bytes still held after the window: {sum(a.nbytes for a in jax.live_arrays())}")
    t = time.perf_counter()
    weights = ref.make_weights(spec, seed, rows)
    gaps, controls = [], []
    for prompt, served in sample:
        if control:
            g, c = ref.control_gap(weights, spec, prompt, served)
            controls.append(c)
        else:
            g = ref.served_gap(weights, spec, prompt, served)
        gaps.append(g)
    del weights
    n_tok = sum(len(s) for _, s in sample)
    log(f"reference over {len(sample)} greedy requests, {n_tok} served tokens: {time.perf_counter() - t:.2f}s")
    # with ``control`` the control stands in the program's place: its gaps
    # are the ones judged, the program's are kept for the lower reading
    judged = controls if control else gaps
    limit = float(checks["max_logit_gap"]["limit"])
    gap = max(judged) if judged else None
    correct = bool(judged) and gap <= limit and failed == 0
    result_checks = {"max_logit_gap": {"value": gap, "limit": limit},
                     "unanswered": {"value": failed, "limit": 0}}
    device = {**dev, "memory_peak_bytes": memory_peak}
    if traced is not None:
        device["busy_s"], device["window_s"] = traced["busy_s"], traced["window_s"]
    result = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["gaps"], result["control_gaps"], result["compared_tokens"] = gaps, controls, n_tok
    result["checks"] = result_checks
    for k, v in result_checks.items():
        print(f"[check] {k} {v['value']} limit {v['limit']}", file=sys.stderr, flush=True)
    return result


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="weights, traffic and sampling seed")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0,
                    help="1: trace the window and report the per-layer metrics")
    args = ap.parse_args(argv)
    result = run_cell(registry.ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
