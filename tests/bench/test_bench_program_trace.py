"""The program-trace reduction (bench/program_trace.py): scope paths read
from the trace's wire format, idle gaps given to the innermost host span,
engine span totals, and the keys it shares with bench/trace.py left as
that reduction gives them."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import program_trace as pt
from bench import trace

DATA = Path(__file__).parent / "data"


def _ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


OP = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x)"
SAMPLE_OP = "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %y)"
COPY_OP = "%copy.3 = bf16[8]{0} copy(bf16[8]{0} %z)"


def synthetic():
    """Two programs and the host spans of one tick, in ns.  Busy: [150, 210)
    and [380, 395); the rest of the window [0, 1000) is idle."""
    device = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit__decode_impl(7)", 150, 210), _ev("jit__prefill_impl(9)", 380, 395)],
        "XLA Ops": [_ev(OP, 150, 180), _ev(SAMPLE_OP, 180, 200), _ev(COPY_OP, 200, 210),
                    _ev(OP, 380, 395)],  # the same op name in the other program
    })
    host = _plane("/host:CPU", {"python": [
        _ev("bench.window", 0, 1000), _ev("bench.submit", 10, 50), _ev("bench.step", 60, 400),
        _ev("engine.step", 70, 398), _ev("engine.admit", 72, 90), _ev("engine.prepare", 90, 150),
        _ev("engine.spill", 100, 140), _ev("engine.dispatch", 150, 160), _ev("engine.wait", 160, 300),
        _ev("engine.emit", 300, 350), _ev("bench.poll", 400, 500), _ev("unrelated", 0, 1000),
    ]})
    paths = {(7, OP): "jit(_decode_impl)/while/body/attn/dot_general:",
             (7, SAMPLE_OP): "jit(_decode_impl)/sample/sort:",
             (9, OP): "jit(_prefill_impl)/proj/mlp/dot_general:"}
    return [host, device], paths


def test_idle_goes_to_the_innermost_span():
    planes, paths = synthetic()
    got = pt.reduce_planes(planes, paths)
    want = {"host.other": 520, "engine.wait": 90, "bench.poll": 100, "engine.emit": 50, "engine.spill": 40,
            "bench.submit": 40, "engine.step": 35, "engine.prepare": 20, "engine.admit": 18, "bench.step": 12}
    assert dict(got["idle_gaps"]) == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    values = [v for _, v in got["idle_gaps"]]
    assert values == sorted(values, reverse=True)
    # every idle second is attributed once, as in the old reduction
    old = trace.reduce_xspace(planes)
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(sum(v for _, v in old["idle_gaps"]))
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(got["window_s"] - got["busy_s"])
    assert dict(old["idle_gaps"])["bench.step"] == pytest.approx(265e-9)  # before: all of engine.step too


def test_engine_span_totals():
    planes, paths = synthetic()
    spans = pt.reduce_planes(planes, paths)["spans"]
    assert set(spans) == {"engine.step", "engine.admit", "engine.prepare", "engine.spill", "engine.dispatch",
                          "engine.wait", "engine.emit"}
    assert spans["engine.step"] == {"count": 1, "host_s": pytest.approx(328e-9), "idle_s": pytest.approx(35e-9)}
    assert spans["engine.prepare"] == {"count": 1, "host_s": pytest.approx(60e-9), "idle_s": pytest.approx(20e-9)}
    assert spans["engine.dispatch"] == {"count": 1, "host_s": pytest.approx(10e-9), "idle_s": 0.0}


def test_device_time_splits_by_innermost_scope_per_program():
    planes, paths = synthetic()
    got = pt.reduce_planes(planes, paths)
    assert got["scopes"] == {
        "jit__decode_impl": {"attn": pytest.approx(30e-9), "sample": pytest.approx(20e-9),
                             "unscoped": pytest.approx(10e-9)},
        "jit__prefill_impl": {"mlp": pytest.approx(15e-9)},
    }
    # the split adds up to the self time of the program's ops
    old = trace.reduce_xspace(planes)
    for module, per in got["scopes"].items():
        assert sum(per.values()) == pytest.approx(sum(v for k, v in old["ops"] if k.startswith(module + "/")))


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/dot_general:", "unscoped"),
    ("jit(_decode_impl)/while/body/attn/dot_general:", "attn"),
    ("jit(_decode_impl)/proj/mlp/dot_general:", "mlp"),
    ("jit(_decode_impl)/while/body/closed_call/sample:", "sample"),
    ("jit(f)/attention/dot_general:", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of(path, scope):
    assert pt.scope_of(path) == scope


def _vint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _int(num: int, v: int) -> bytes:
    return _vint(num << 3) + _vint(v)


def _msg(num: int, payload: bytes) -> bytes:
    return _vint(num << 3 | 2) + _vint(len(payload)) + payload


def test_wire_reader_follows_string_and_reference_stats():
    """A hand-encoded XSpace: one device plane whose op metadata holds the
    program id as a uint64 and tf_op either as a string or as a reference
    to a stat metadata name; a host plane and the event lines are skipped."""
    def stat_meta(i, name):
        return _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, name.encode())))

    def event_meta(i, name, stats):
        return _msg(4, _int(1, i) + _msg(2, _int(1, i) + _msg(2, name.encode()) + b"".join(stats)))

    program = _msg(5, _int(1, 1) + _int(3, 2**63 + 5))
    plane = (_int(1, 3) + _msg(2, b"/device:TPU:0") + _msg(3, b"\x08\x01 lines are skipped")
             + stat_meta(1, "program_id") + stat_meta(2, "tf_op") + stat_meta(3, "jit(f)/head/dot_general:")
             + event_meta(10, "%a", [program, _msg(5, _int(1, 2) + _msg(5, b"jit(f)/attn/add:"))])
             + event_meta(11, "%b", [program, _msg(5, _int(1, 2) + _int(7, 3))])
             + event_meta(12, "%c", [program]))
    host = _msg(2, b"/host:CPU") + stat_meta(2, "tf_op") + event_meta(1, "%h", [_msg(5, _int(1, 2) + _msg(5, b"x"))])
    space = _msg(1, plane) + _msg(1, host) + _msg(4, b"hostname")
    assert pt.op_paths(space) == {(2**63 + 5, "%a"): "jit(f)/attn/add:", (2**63 + 5, "%b"): "jit(f)/head/dot_general:"}


def test_recorded_trace_paths_and_unchanged_keys():
    """The v5e trace of bench/trace.py's tests: its one fusion carries the
    path ``jit(step)/dot_general:``, and every key the old reduction gives
    keeps its value."""
    path = DATA / "decode_steps.xplane.pb"
    paths = pt.op_paths(path.read_bytes())
    assert list(paths.values()) == ["jit(step)/dot_general:"]
    assert next(iter(paths))[0] == 9316458401576507835
    old, new = trace.reduce_file(path), pt.reduce_file(path)
    assert {k: new[k] for k in old} == old
    assert new["scopes"] == {"jit_step": {"unscoped": pytest.approx(old["busy_s"])}}
    assert new["spans"] == {}


def test_recorded_scoped_trace_reduces_to_expected():
    """A v5e trace of ``record_scoped_trace.py``: four calls of a step with
    a ``proj`` and an ``mlp`` scope inside nested spans, with 10 ms of host
    work in ``engine.spill`` per call, 5 ms each in the rest of
    ``engine.prepare``, in ``engine.emit`` and under no span, 3 ms each in
    ``bench.step`` outside the engine and in ``bench.poll``."""
    import json

    path = DATA / "scoped_steps.xplane.pb"
    got = pt.reduce_file(path)
    want = json.loads((DATA / "scoped_steps.expected.json").read_text())
    assert got["scopes"] == {m: {k: pytest.approx(v) for k, v in per.items()} for m, per in want["scopes"].items()}
    assert got["spans"] == {n: {k: pytest.approx(v) for k, v in row.items()} for n, row in want["spans"].items()}
    assert got["idle_gaps"] == [[k, pytest.approx(v)] for k, v in want["idle_gaps"]]
    old = trace.reduce_file(path)
    assert {k: got[k] for k in ("window_s", "devices", "busy_s", "modules", "ops")} == {
        k: old[k] for k in ("window_s", "devices", "busy_s", "modules", "ops")}
    gaps = dict(got["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(sum(v for _, v in old["idle_gaps"]), rel=1e-9)
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-9)
    # the host work, by the span innermost to it
    for name, ms in (("engine.spill", 40), ("engine.prepare", 20), ("engine.emit", 20), ("host.other", 20),
                     ("bench.step", 12), ("bench.poll", 12)):
        assert gaps[name] == pytest.approx(ms * 1e-3, rel=0.02), name
    assert dict(old["idle_gaps"])["bench.step"] == pytest.approx(sum(
        v for k, v in gaps.items() if k == "bench.step" or k.startswith("engine.")))
    assert {n: row["count"] for n, row in got["spans"].items()} == {
        n: 4 for n in ("engine.step", "engine.prepare", "engine.spill", "engine.dispatch", "engine.wait",
                       "engine.emit")}
    # both scopes hold device time; only the weight copy has no scope
    scopes = got["scopes"]["jit_step"]
    assert scopes["proj"] > 0 and scopes["mlp"] > 0
    assert scopes["unscoped"] < 0.01 * got["busy_s"]
    assert sum(scopes.values()) == pytest.approx(got["busy_s"], rel=0.01)
