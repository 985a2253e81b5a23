"""Work counts (bench/work/dense.py) against hand arithmetic at both
configurations, the same count on the XLA and the Pallas datapaths, and
the peaks table."""
from __future__ import annotations

import json

import pytest

from bench import registry
from bench.model import spec_from_file

work = registry.family_module(registry.ROOT, "work", "dense")
V5E = registry.peaks(registry.ROOT, "TPU v5 lite")


def spec(name):
    return spec_from_file(registry.config_file(registry.ROOT, name))


def test_qwen3_4b_decode_step_by_hand():
    s = spec("qwen3-4b")
    # one layer: q,k,v 2560*128*(32+2*8) + o 32*128*2560 + gate,up,down 3*2560*9728
    assert s.layer_params == 15_728_640 + 10_485_760 + 74_711_040 == 100_925_440
    # 36 layers + tied head 2560*151936, bf16; f32 norms (2*36+1)*2560 + q/k norms 2*36*128
    assert work.weight_bytes(s) == (36 * 100_925_440 + 2560 * 151_936) * 2 + (73 * 2560 + 72 * 128) * 4
    assert work.weight_bytes(s) == 8_045_328_384
    flops, nbytes = work.decode_step(s, [100, 1000], 1)
    # per token 2*36*100925440 + head 2*2560*151936; attention 4*36*32*128 per live position
    assert flops == 2 * (7_266_631_680 + 777_912_320) + 589_824 * 1100 == 16_737_894_400
    # weights once, K/V 36 layers * 2*8*128*2 B per live position read, and per new token written
    assert nbytes == 8_045_328_384 + (1100 + 2) * 147_456 == 8_207_824_896
    t, bound = work.least_time(flops, nbytes, V5E)
    assert bound == "hbm" and t == pytest.approx(8_207_824_896 / 819e9)


def test_deepseek_7b_decode_step_by_hand():
    s = spec("deepseek-7b")
    assert s.layers == 15 and s.kv_bytes_per_token_layer == 2 * 32 * 128 * 2 == 16_384
    assert s.layer_params == 4096 * 128 * 96 + 32 * 128 * 4096 + 3 * 4096 * 11008 == 202_375_168
    # untied: the head is read whole, the embedding only row by row
    assert work.weight_bytes(s) == (15 * 202_375_168 + 4096 * 102_400) * 2 + 31 * 4096 * 4 == 6_910_623_744
    flops, nbytes = work.decode_step(s, [2000] * 8, 1)
    assert flops == 8 * (2 * 15 * 202_375_168 + 2 * 4096 * 102_400 + 4 * 15 * 32 * 128 * 2000) == 59_213_086_720
    assert nbytes == 6_910_623_744 + (16_000 + 8) * 15 * 16_384 == 10_844_749_824
    t, bound = work.least_time(flops, nbytes, V5E)
    assert bound == "hbm" and t == pytest.approx(0.0132414527765)


def test_substeps_read_the_weights_each_time():
    s = spec("qwen3-4b")
    one = work.decode_step(s, [10, 11], 1)
    two = work.decode_step(s, [10, 11], 2)
    assert two[0] == one[0] and two[1] - one[1] == work.weight_bytes(s)


def test_prefill_flops_by_hand():
    s = spec("deepseek-7b")
    # positions 3..6 attend over 4+5+6+7 = 22 entries; one row emits a token
    got = work.prefill_flops(s, [(3, 7)], heads=1)
    assert got == 4 * 2 * 15 * 202_375_168 + 4 * 15 * 32 * 128 * 22 + 2 * 4096 * 102_400
    assert work.prefill_flops(s, [], 0) == 0


def test_least_time_names_the_bound():
    assert work.least_time(197e12, 1.0, V5E) == (pytest.approx(1.0), "flops")
    assert work.least_time(1.0, 819e9, V5E) == (pytest.approx(1.0), "hbm")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        registry.peaks(registry.ROOT, "TPU v9 imaginary")
    table = json.loads((registry.bench_dir(registry.ROOT) / "peaks.json").read_text())
    assert table["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def _tick_work(root, use_pallas: bool, ticks: int):
    import dataclasses

    from bench import serve
    from bench.traffic import Traffic

    cfg_file = registry.config_file(root, "qwen3-smoke")
    s = spec_from_file(cfg_file)
    mc = serve.model_config(cfg_file)
    ref = registry.family_module(root, "reference", "dense")
    params = serve.program_params(ref.make_weights(s, 5, serve.vocab_rows(mc)), mc)
    engine = serve.build_engine(mc, params, cfg_file["deployment"])
    if use_pallas:
        from repro.serve.engine import ContinuousServeEngine

        engine = ContinuousServeEngine(mc, params, dataclasses.replace(engine.scfg, use_pallas=True))
    traffic = Traffic(registry.mix_file(root, "tinychat"), s.vocab, engine.scfg.slots, 5)
    loop = serve.ClosedLoop(engine, traffic)
    out = []
    for _ in range(ticks):
        t = loop.tick()
        out.append((t.kind, work.decode_step(s, t.kv_lens, t.substeps) if t.kind == "decode" else None,
                    work.prefill_flops(s, t.prefill_spans, t.first_tokens)))
    return out


def test_same_count_on_xla_and_pallas_datapaths(smoke_root):
    """The count comes from lengths the request handles show, not from the
    datapath: the gather path and the Pallas kernel read the same work."""
    xla = _tick_work(smoke_root, False, 40)
    pallas = _tick_work(smoke_root, True, 40)
    assert xla == pallas
    assert {k for k, _, _ in xla} >= {"decode", "prefill"}
