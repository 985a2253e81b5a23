"""The trace reduction (bench/trace.py) on a small trace recorded on a TPU v5e
(four executions of one jitted matmul step inside a ``bench.window`` span)
and on synthetic planes."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def test_recorded_v5e_trace_reduces_to_expected():
    got = trace.reduce_file(DATA / "decode_steps.xplane.pb")
    want = json.loads((DATA / "decode_steps.expected.json").read_text())
    assert got["devices"] == want["devices"] == 1
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["modules"] == {k: pytest.approx(v) for k, v in want["modules"].items()}
    assert got["ops"] == [[k, pytest.approx(v)] for k, v in want["ops"]]
    assert got["idle_gaps"] == [[k, pytest.approx(v)] for k, v in want["idle_gaps"]]
    # the window's time is either busy or an attributed idle gap
    assert got["busy_s"] + sum(v for _, v in got["idle_gaps"]) == pytest.approx(got["window_s"], rel=1e-9)


def test_recorded_trace_matches_hand_sums():
    """Hand sums over the recorded events: four executions of ``jit_step``
    of 7286 + 7032 + 7043 + 7036 ns; their ops (copy-start, copy-done,
    fusion) of 7278 + 7026 + 7037 + 7029 ns; the window span 24,226,539 ns."""
    got = trace.reduce_file(DATA / "decode_steps.xplane.pb")
    assert trace.module_time(got, "jit_step") == (4, pytest.approx(28397e-9))
    assert got["busy_s"] == pytest.approx(28370e-9)
    assert got["window_s"] == pytest.approx(24226539e-9)
    assert got["ops"][0][0] == "jit_step/%fusion bf16[256,1024] fusion"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def test_synthetic_union_window_and_gap_attribution():
    device = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit__decode_impl(1)", 100, 50), _ev("jit__decode_impl(1)", 300, 40),
                        _ev("jit__prefill_impl(2)", 900, 500)],  # starts outside the window
        "XLA Ops": [_ev("%a = f32[2]{0} add(f32[2]{0} %x)", 100, 30),
                    _ev("%b = f32[2]{0} multiply(f32[2]{0} %x)", 120, 30),  # overlaps %a
                    _ev("%a = f32[2]{0} add(f32[2]{0} %x)", 300, 40),
                    _ev("%c = f32[2]{0} exp(f32[2]{0} %x)", 950, 100)],  # past the window
    })
    host = _plane("/host:CPU", {"python": [
        _ev("bench.window", 50, 850),  # [50, 900)
        _ev("bench.step", 60, 250),  # [60, 310)
        _ev("bench.poll", 400, 100),  # [400, 500)
        _ev("unrelated", 0, 1000),
    ]})
    got = trace.reduce_xspace([host, device, _plane("/device:CUSTOM:x", {})])
    assert got["window_s"] == pytest.approx(850e-9)
    # busy = [100, 150) u [300, 340) = 90 ns
    assert got["busy_s"] == pytest.approx(90e-9)
    assert got["modules"] == {"jit__decode_impl": [2, pytest.approx(90e-9)]}
    assert got["ops"] == [["jit__decode_impl/%a f32[2] add", pytest.approx(70e-9)],
                          ["jit__decode_impl/%b f32[2] multiply", pytest.approx(30e-9)]]
    gaps = dict(got["idle_gaps"])
    # idle: [50,100) [150,300) [340,900); bench.step covers [60,100) and [150,300)
    assert gaps["bench.step"] == pytest.approx(190e-9)
    assert gaps["bench.poll"] == pytest.approx(100e-9)
    assert gaps["host.other"] == pytest.approx((10 + 560 - 100) * 1e-9)


def test_ops_are_counted_by_self_time():
    """A layer loop's event encloses its body's ops: the loop keeps only
    the time no body op covers."""
    device = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit_f(1)", 0, 1000)],
        "XLA Ops": [_ev("%while.1 = s32[] while(s32[] %i)", 0, 1000),
                    _ev("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %x)", 100, 300),
                    _ev("%convert.3 = f32[8]{0} convert(bf16[8]{0} %x)", 150, 100),  # inside %fusion.2
                    _ev("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %x)", 500, 300)],
    })
    got = trace.reduce_xspace([device])
    assert got["busy_s"] == pytest.approx(1000e-9)
    assert got["ops"] == [["jit_f/%fusion.2 bf16[8] fusion", pytest.approx(500e-9)],
                          ["jit_f/%while.1 s32[] while", pytest.approx(400e-9)],
                          ["jit_f/%convert.3 f32[8] convert", pytest.approx(100e-9)]]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce_xspace([_plane("/host:CPU", {"python": [_ev("bench.window", 0, 10)]})])


def test_labels():
    assert trace.module_label("jit__decode_impl(9316458401576507835)") == "jit__decode_impl"
    name = ("%copy-start = (bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)}, bf16[1024,1024]{1,0}, u32[]{:S(2)}) "
            "copy-start(bf16[1024,1024]{1,0} %w.1)")
    assert trace.op_label(name) == "%copy-start bf16[1024,1024] copy-start"
