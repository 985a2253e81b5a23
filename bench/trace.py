"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Within the traced window (the host span ``bench.window``):

* ``busy_s``: the union of the intervals in which an XLA op ran on a
  device, averaged over the devices;
* ``modules``: per XLA module (the compiled program, hash stripped), its
  executions that start in the window and their device seconds;
* ``ops``: self seconds per op (its time less that of the ops nested in
  it, such as a layer loop's body), labelled ``<module>/<op>``, largest
  first;
* ``idle_gaps``: the seconds in which no op ran, split by the benchmark's
  host span that covers them (``bench.step``, ``bench.poll``,
  ``bench.submit``; ``host.other`` where none does).

Device and host events share the trace's clock, up to an offset of about
a millisecond between the planes; gaps are read at that resolution.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.step", "bench.poll", "bench.submit")
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OP_NAME = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])")
_OP_CODE = re.compile(r"[}\]]\)? ([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``%fusion.3 bf16[8,128] fusion``."""
    m, code = _OP_NAME.match(name), _OP_CODE.search(name)
    if not (m and code):
        return name[:120]
    return f"{m.group(1)} {m.group(2).lstrip('(')} {code.group(1)}"


def module_label(name: str) -> str:
    """``jit__decode_impl(123456)`` -> ``jit__decode_impl``."""
    return name.split("(", 1)[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if min(e, hi) > max(s, lo)]


def _overlap(a: tuple[float, float], spans: list[tuple[float, float]]) -> float:
    return sum(max(0.0, min(a[1], e) - max(a[0], s)) for s, e in spans)


def _self_times(ops, modules, lo, hi):
    """(``<module>/<op>`` label, self ns in the window) of each op event.
    Events of one device line nest; an op's self time is its time less the
    time of the ops directly inside it."""
    mods = sorted((s, e, module_label(n)) for s, e, n in modules)
    starts = [m[0] for m in mods]
    out, stack = [], []  # stack: (start, end, index into out) of the ops enclosing this one
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and not (stack[-1][0] <= s and e <= stack[-1][1]):
            stack.pop()
        d = min(e, hi) - max(s, lo)
        i = bisect.bisect_right(starts, s) - 1
        module = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append([f"{module}/{op_label(name)}", max(d, 0.0)])
        if stack:
            out[stack[-1][2]][1] -= max(d, 0.0)
        stack.append((s, e, len(out) - 1))
    return [(label, d) for label, d in out if d > 0]


def reduce_xspace(planes, top: int = 10) -> dict:
    """Reduce decoded planes (``jax.profiler.ProfileData(...).planes``)."""
    devices, host = [], {}
    for plane in planes:
        if _DEVICE.match(plane.name):
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                     for line in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        host.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    if not devices:
        raise ValueError("trace holds no device plane")
    if WINDOW_SPAN in host:
        lo, hi = host[WINDOW_SPAN][0]
    else:
        ops = [(s, e) for d in devices for s, e, _ in d.get("XLA Ops", [])]
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    window_ns = hi - lo
    busy_ns, modules, ops_ns, gaps = 0.0, {}, {}, {}
    spans = {name: _union(_clip(iv, lo, hi)) for name, iv in host.items() if name in HOST_SPANS}
    for lines in devices:
        ops = lines.get("XLA Ops", [])
        busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for s, e, name in lines.get("XLA Modules", []):
            if lo <= s < hi:
                m = modules.setdefault(module_label(name), [0, 0.0])
                m[0] += 1
                m[1] += (e - s) * 1e-9
        for label, d in _self_times(ops, lines.get("XLA Modules", []), lo, hi):
            ops_ns[label] = ops_ns.get(label, 0.0) + d
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            rest = ge - gs
            for name, iv in spans.items():
                covered = _overlap((gs, ge), iv)
                gaps[name] = gaps.get(name, 0.0) + covered
                rest -= covered
            gaps["host.other"] = gaps.get("host.other", 0.0) + max(rest, 0.0)
    n = len(devices)
    return {
        "window_s": window_ns * 1e-9,
        "devices": n,
        "busy_s": busy_ns * 1e-9 / n,
        "modules": {k: [c, s / n] for k, (c, s) in sorted(modules.items())},
        "ops": [[k, v * 1e-9 / n] for k, v in sorted(ops_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * 1e-9 / n] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1]) if v > 0][:top],
    }


def reduce_file(path: Path, top: int = 10) -> dict:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return reduce_xspace(ProfileData.from_file(str(path)).planes, top)


def find_xplane(log_dir: Path) -> Path:
    """The one ``.xplane.pb`` that ``jax.profiler`` wrote under ``log_dir``."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {len(found)}")
    return found[0]


def module_time(reduced: dict, name: str) -> tuple[int, float]:
    """(executions, device seconds) of the XLA module ``name`` in the window."""
    count, secs = reduced["modules"].get(name, (0, 0.0))
    return int(count), float(secs)


def ms_per_execution(ctx, name: str) -> float | None:
    """Device milliseconds per execution of the XLA module ``name`` in the
    traced window; None without a trace or an execution."""
    if ctx.trace is None:
        return None
    n, secs = module_time(ctx.trace, name)
    return 1e3 * secs / n if n else None
