"""The harness on the CPU at smoke size: traffic generation, the metric
arithmetic, the reference against the served tokens, lookup by name, and
the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

from bench import registry
from bench.run import run_cell
from bench.serve import Record, Tick
from bench.stats import Ctx
from bench.traffic import Traffic, deck

REPO = registry.ROOT


def mix(name):
    return registry.mix_file(REPO, name)


@pytest.mark.parametrize("name,vocab,slots", [("chat", 151936, 32), ("docqa", 102400, 8)])
def test_traffic_is_deterministic_per_seed_and_within_clips(name, vocab, slots):
    m = mix(name)
    a = [Traffic(m, vocab, slots, 2**31 + 9).next() for _ in range(1)]
    t1, t2, t3 = (Traffic(m, vocab, slots, s) for s in (2**31 + 9, 2**31 + 9, 7))
    r1 = [t1.next() for _ in range(3 * t1.k)]
    r2 = [t2.next() for _ in range(3 * t2.k)]
    r3 = [t3.next() for _ in range(3 * t3.k)]
    assert r1 == r2 and r1[0] == a[0]
    assert r1 != r3
    docs = {d: len(x) for d, x in enumerate(t1.documents)}
    for r in r1:
        own = len(r.prompt) - docs.get(r.document, 0)
        assert m["prompt"]["min"] <= own <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
        assert all(0 < tok < vocab for tok in r.prompt)
        assert r.greedy == (r.temperature == 0.0)
    # every seed sends the same sizes, one deck per block, in another order
    for rs in (r1, r3):
        for b in range(3):
            block = rs[b * t1.k:(b + 1) * t1.k]
            docs_b = {d: len(x) for d, x in enumerate((t1 if rs is r1 else t3).documents)}
            assert sorted(len(r.prompt) - docs_b.get(r.document, 0) for r in block) == sorted(t1.prompts)
            assert sorted(r.max_new for r in block) == sorted(t1.outputs)
            assert sum(r.greedy for r in block) == round(m["greedy_share"] * t1.k)
    assert [r.max_new for r in r1] != [r.max_new for r in r3]
    # any 8 consecutive requests hold one prompt from each eighth of the deck
    for start in range(2 * t1.k):
        strata = {t1.level(n) // (t1.k // 8) for n in range(start, start + 8)}
        assert strata == set(range(8))
    with pytest.raises(ValueError, match="power of two"):
        Traffic(dict(m, deck=24), vocab, slots, 1)


def test_docqa_shares_documents():
    m = mix("docqa")
    t = Traffic(m, 102400, 8, 3)
    assert len(t.documents) == m["documents"]["count"]
    assert all(m["documents"]["min"] <= len(d) <= m["documents"]["max"] for d in t.documents)
    rs = [t.next() for _ in range(4 * len(t.documents))]
    for r in rs:
        assert r.prompt[: len(t.documents[r.document])] == t.documents[r.document]
    # each run of `count` requests asks about every document once
    for b in range(4):
        assert sorted(r.document for r in rs[b * 6:(b + 1) * 6]) == list(range(6))
    t.check_fits(3072, 1024, 16)
    with pytest.raises(ValueError, match="exceed the pool"):
        t.check_fits(3072, 700, 16)


def test_deck_quantiles():
    assert deck({"dist": "uniform", "min": 16, "max": 64}, 4) == [22, 34, 46, 58]
    d = deck({"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 64, "max": 1536}, 33)
    assert d[16] == 384 and d == sorted(d) and d[-1] == 1536 and min(d) >= 64


def _rec(send, times, done=None, prompt=4, greedy=True):
    from bench.traffic import Req

    return Record(req=None, sent=Req(prompt=(1,) * prompt, max_new=len(times) or 1, greedy=greedy),
                  client=0, send_t=send, times=list(times), done_t=done)


def test_metric_arithmetic_on_synthetic_stamps():
    records = [
        _rec(5.0, [9.0, 11.0, 12.0, 14.0], done=14.0),  # sent before the window: no TTFT
        _rec(10.0, [10.5, 11.0, 11.5], done=11.5),  # TTFT 0.5
        _rec(12.0, [15.0, 16.0, 21.0, 22.0]),  # TTFT 3.0; gap 21-16 crosses the close
        _rec(19.0, [23.0]),  # sent in the window, first token after the close: TTFT 4.0
        _rec(20.5, [21.0]),  # sent after the close
    ]
    ctx = Ctx(records=records, ticks=[], window=(10.0, 20.0), setup_s=3.5, spec=None, work=None, peak=None)

    def read(name):
        return registry.metric_reader(REPO, name).read(ctx)

    # tokens stamped in [10, 20]: 11,12,14 + 10.5,11,11.5 + 15,16 = 8
    assert read("tokens_per_s") == pytest.approx(8 / 10)
    # TTFTs of the requests sent in the window: 0.5, 3.0, 4.0
    assert read("ttft_p50_ms") == pytest.approx(3000.0)
    assert read("ttft_mean_ms") == pytest.approx(2500.0)
    # gaps with both tokens in the window: 1, 2, 0.5, 0.5, 1
    assert ctx.gaps_s() == pytest.approx([1.0, 2.0, 0.5, 0.5, 1.0])
    assert read("itl_p50_ms") == pytest.approx(1000.0)
    assert read("itl_p95_ms") == pytest.approx(1e3 * float(np.percentile([1, 2, 0.5, 0.5, 1], 95)))
    assert read("setup_s") == 3.5
    # no trace: the per-layer readers find nothing to read
    for name in ("device_idle", "decode_step_ms", "prefill_chunk_ms", "prefill_chunk_ms.docqa", "decode_roofline",
                 "mfu", "decode_rows", "prefix_hit_share"):
        assert read(name) is None


def test_per_layer_arithmetic_on_a_synthetic_trace():
    from bench.model import spec_from_file

    spec = spec_from_file(registry.config_file(REPO, "deepseek-7b"))
    work = registry.family_module(REPO, "work", "dense")
    peak = registry.peaks(REPO, "TPU v5 lite")
    ticks = [Tick(t0=1.0, t1=1.1, kind="decode", decode_rows=2, substeps=1, kv_lens=[100, 200]),
             Tick(t0=1.1, t1=1.2, kind="prefill", prefill_spans=[(0, 16)], first_tokens=1),
             Tick(t0=1.2, t1=1.3, kind="decode", decode_rows=4, substeps=1, kv_lens=[1, 2, 3, 4]),
             Tick(t0=2.5, t1=2.6, kind="decode", decode_rows=8, substeps=1, kv_lens=[5] * 8)]  # after the trace
    req = type("R", (), {"shared_tokens": 30})()
    rec = _rec(1.05, [1.2], prompt=40)
    rec.req = req
    reduced = {"window_s": 1.0, "busy_s": 0.9, "devices": 1, "ops": [], "idle_gaps": [],
               "modules": {"jit__decode_impl": [2, 0.2], "jit__prefill_impl": [1, 0.05]}}
    ctx = Ctx(records=[rec], ticks=ticks, window=(1.0, 2.0), setup_s=1.0, spec=spec, work=work, peak=peak,
              trace=reduced, trace_window=(1.0, 2.0))

    def read(name):
        got = registry.metric_reader(REPO, name).read(ctx)
        return got["value"] if isinstance(got, dict) else got

    assert read("device_idle") == pytest.approx(10.0)
    assert read("decode_step_ms") == pytest.approx(100.0)
    assert read("prefill_chunk_ms") == pytest.approx(50.0)
    assert read("prefill_chunk_ms.docqa") == pytest.approx(50.0)
    assert read("decode_rows") == pytest.approx(3.0)
    assert read("prefix_hit_share") == pytest.approx(75.0)
    least = sum(work.least_time(*work.decode_step(spec, k, 1), peak)[0] for k in ([100, 200], [1, 2, 3, 4]))
    assert read("decode_roofline") == pytest.approx(100 * least / 0.2)
    flops = (work.decode_step(spec, [100, 200], 1)[0] + work.decode_step(spec, [1, 2, 3, 4], 1)[0]
             + work.prefill_flops(spec, [(0, 16)], 1))
    assert read("mfu") == pytest.approx(100 * flops / 197e12)


def test_smoke_cells_run_correct_through_the_harness(smoke_root):
    """The smoke twins of both cells, found by their file names, run end to
    end on the CPU; the reference agrees with the engine's prefill and
    paged decode on every sampled greedy token."""
    for cell, ttft in (("qwen3-smoke.chat", "ttft_p50_ms"), ("deepseek-smoke.docqa", "ttft_mean_ms")):
        r = run_cell(smoke_root, cell, 2**31 + 11, 1.5, False, require_chip=False)
        assert r["correct"] is True, r["checks"]
        assert r["attempted"] > 0 and r["failed"] == 0
        assert set(r["metrics"]) == {"tokens_per_s", ttft, "itl_p50_ms", "itl_p95_ms", "setup_s"}
        assert list(r)[-1] == "checks"
        assert 0 <= r["checks"]["max_logit_gap"]["value"] <= r["checks"]["max_logit_gap"]["limit"]


def test_traced_run_reports_the_per_layer_metrics(smoke_root, monkeypatch):
    """A ``--trace 1`` run on the CPU, with the reduction of a CPU trace
    (which holds no TPU plane) replaced by a fixed one."""
    from bench import trace

    fixed = {"window_s": 1.5, "busy_s": 1.2, "devices": 1, "ops": [["%f f32[1] fusion", 1.0]],
             "idle_gaps": [["bench.step", 0.3]],
             "modules": {"jit__decode_impl": [100, 0.6], "jit__prefill_impl": [50, 0.5]}}
    monkeypatch.setattr(trace, "reduce_file", lambda path, top=10: fixed)
    monkeypatch.setattr(registry, "peaks", lambda root, kind: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    r = run_cell(smoke_root, "deepseek-smoke.docqa", 3, 1.5, True, require_chip=False)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"device_idle", "decode_step_ms", "prefill_chunk_ms.docqa", "decode_roofline", "mfu",
                                 "decode_rows", "prefix_hit_share"}
    assert r["metrics"]["device_idle"]["value"] == pytest.approx(20.0)
    assert r["metrics"]["decode_step_ms"]["value"] == pytest.approx(6.0)
    assert r["metrics"]["prefill_chunk_ms.docqa"]["value"] == pytest.approx(10.0)
    assert r["metrics"]["prefix_hit_share"]["value"] > 50.0
    assert r["device"]["busy_s"] == 1.2 and r["device"]["window_s"] == 1.5
    assert r["breakdown"] == {"device_ops": fixed["ops"], "idle_gaps": fixed["idle_gaps"]}


def test_new_config_mix_and_metric_need_no_edit(smoke_root, tmp_path):
    """A configuration, a traffic mix and a metric added as new files (and
    entries in BENCHMARK.json) are found by name; no existing file of the
    benchmark changes."""
    import hashlib
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(smoke_root, root, symlinks=True)
    b = root / "bench"

    def digests():
        return {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in sorted(b.rglob("*")) if p.is_file()
                and "__pycache__" not in p.parts}

    before = digests()
    cfg = json.loads((b / "configs" / "qwen3-smoke.json").read_text())
    cfg.update(name="qwen3-smoke-mha", num_key_value_heads=8)
    (b / "configs" / "qwen3-smoke-mha.json").write_text(json.dumps(cfg))
    m = json.loads((b / "traffic" / "tinychat.json").read_text())
    m.update(output={"dist": "uniform", "min": 2, "max": 6})
    (b / "traffic" / "shortchat.json").write_text(json.dumps(m))
    (b / "metrics" / "requests_per_s.py").write_text(
        "def read(ctx):\n    return len(ctx.sent_in_window()) / ctx.window_s\n")
    (b / "checks" / "qwen3-smoke-mha.shortchat.json").write_text(json.dumps({"max_logit_gap": {"limit": 0.08}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen3-smoke-mha", "source": "x", "file": "bench/configs/qwen3-smoke-mha.json",
                             "reduced": cfg["reduced"], "why": "new"})
    bench["workloads"].append({"name": "qwen3-smoke-mha.shortchat", "config": "qwen3-smoke-mha",
                               "traffic": "shortchat", "chips": 1, "why": "new"})
    bench["end_to_end"].append({"name": "requests_per_s", "unit": "requests/s", "better": "higher", "bound": 0.1,
                                "source": "host_clock", "workloads": ["qwen3-smoke-mha.shortchat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run_cell(root, "qwen3-smoke-mha.shortchat", 21, 1.0, False, require_chip=False)
    assert r["correct"] is True
    assert r["metrics"]["requests_per_s"]["value"] > 0
    assert r["metrics"]["requests_per_s"]["unit"] == "requests/s"
    after = digests()
    assert {p: d for p, d in after.items() if p in before} == before


def test_without_a_tpu_the_run_refuses():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload", "qwen3-4b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert '"correct": true' not in p.stdout and '"correct"' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_cell_metrics_follow_workloads_lists():
    bench = registry.load_benchmark(REPO)
    e2e, per_layer = registry.cell_metrics(bench, "qwen3-4b.chat")
    assert [m["name"] for m in e2e] == ["tokens_per_s", "ttft_p50_ms", "itl_p50_ms", "itl_p95_ms", "setup_s"]
    assert "prefix_hit_share" not in {m["name"] for m in per_layer}
    e2e, per_layer = registry.cell_metrics(bench, "deepseek-7b.docqa")
    assert [m["name"] for m in e2e] == ["tokens_per_s", "ttft_mean_ms", "itl_p50_ms", "itl_p95_ms", "setup_s"]
    assert "prefix_hit_share" in {m["name"] for m in per_layer}
    # every per-layer metric moves an end-to-end metric its cells report
    for cell in bench["workloads"]:
        e2e, per_layer = registry.cell_metrics(bench, cell["name"])
        assert {m["moves"] for m in per_layer} <= {m["name"] for m in e2e}
    assert Counter(c["config"] for c in bench["workloads"]) == {"qwen3-4b": 1, "deepseek-7b": 1}


def test_sample_spreads_over_slots_and_keeps_the_longest():
    import bench.run as br

    recs = []
    for i in range(12):
        r = _rec(1.0 + i, [2.0 + i] * (5 + i), done=3.0 + i, prompt=10)
        r.req = type("H", (), {"generated": [1] * (5 + i)})()
        r.slot = i % 2 if i < 10 else 2 + i % 2  # slots 2 and 3 only on the last two
        recs.append(r)
    recs[-1].sent = type(recs[-1].sent)(prompt=(1,) * 10, max_new=16, greedy=False)  # sampled: never compared
    sample = br.pick_sample(recs, (0.0, 100.0), seed=4)
    assert sample[0] is recs[-2]  # the longest greedy request
    assert {r.slot for r in sample} == {0, 1, 2}
    assert len(sample) <= br.SAMPLE_REQUESTS
