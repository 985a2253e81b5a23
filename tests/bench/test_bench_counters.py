"""The engine's work counters (``engine.metrics()``) against what the
benchmark derives from request handles (``ClosedLoop._poll``), over a
closed-loop run of each smoke cell: rows per decode step and the prefix
share agree exactly."""
from __future__ import annotations

import pytest

from bench import registry, serve
from bench.model import spec_from_file
from bench.stats import Ctx
from bench.traffic import Traffic

COUNTERS = ("decode_steps", "decode_rows", "prefill_tokens", "prefix_linked_tokens")


def closed_loop_run(root, cell: str, seed: int, ticks: int):
    """The cell's engine with its set-up (documents or pool fill), then
    ``ticks`` closed-loop ticks and a drain until every request is done;
    returns the loop and the counters' growth over the loop."""
    bench = registry.load_benchmark(root)
    cfg_file = registry.config_file(root, registry.workload(bench, cell)["config"])
    dep = cfg_file["deployment"]
    spec = spec_from_file(cfg_file)
    mc = serve.model_config(cfg_file)
    ref = registry.family_module(root, "reference", spec.family)
    engine = serve.build_engine(mc, serve.program_params(ref.make_weights(spec, seed, serve.vocab_rows(mc)), mc), dep)
    mix = registry.mix_file(root, registry.workload(bench, cell)["traffic"])
    traffic = Traffic(mix, spec.vocab, int(dep["slots"]), seed)
    serve.prefill_prompts(engine, traffic.fillers(int(dep["slots"]), int(dep["num_pages"]), engine.scfg.page_size,
                                                  int(dep["max_len"])) + list(traffic.documents))
    before = {k: engine.metrics()[k] for k in COUNTERS}
    loop = serve.ClosedLoop(engine, traffic)
    for _ in range(ticks):
        loop.tick()
    loop.sending = False
    while loop.active:
        loop.tick()
    after = engine.metrics()
    return loop, spec, {k: after[k] - before[k] for k in COUNTERS}


@pytest.mark.parametrize("cell", ["qwen3-smoke.chat", "deepseek-smoke.docqa"])
def test_counters_agree_with_the_closed_loop(smoke_root, cell):
    loop, spec, grew = closed_loop_run(smoke_root, cell, 2**31 + 77, 40)
    decode = [t for t in loop.ticks if t.kind == "decode"]
    assert decode and grew["decode_steps"] == len(decode)
    assert grew["decode_rows"] == sum(t.decode_rows for t in decode)
    assert grew["prefix_linked_tokens"] == sum(r.req.shared_tokens for r in loop.records)
    assert grew["prefill_tokens"] == sum(b - a for t in loop.ticks for a, b in t.prefill_spans)
    # the per-layer readers over the same run, as a traced window covering it
    window = (loop.records[0].send_t, loop.ticks[-1].t1)
    ctx = Ctx(records=loop.records, ticks=loop.ticks, window=window, setup_s=0.0, spec=spec, work=None,
              peak=None, trace_window=window)
    rows = registry.metric_reader(registry.ROOT, "decode_rows").read(ctx)
    assert grew["decode_rows"] / grew["decode_steps"] == pytest.approx(rows, rel=1e-12)
    share = registry.metric_reader(registry.ROOT, "prefix_hit_share").read(ctx)
    linked = grew["prefix_linked_tokens"]
    assert 100.0 * linked / (linked + grew["prefill_tokens"]) == pytest.approx(share, rel=1e-12)
    if cell.startswith("deepseek"):
        assert share > 50.0  # documents come from the prefix cache
