"""A profiler trace (``.xplane.pb``) read by what the program was doing: the
engine's host spans and the step programs' named scopes.

``reduce_file`` returns every key of ``trace.reduce_file``, with the same
values, except that ``idle_gaps`` gives each idle second to the innermost
host span covering it among the benchmark's (``bench.step``, ``bench.poll``,
``bench.submit``) and the engine's (``engine.*``), and lists them all:
``bench.step`` then keeps only what no engine span covers, and the total
is unchanged.  It adds two keys:

* ``scopes``: per XLA module, device self-seconds by the innermost of the
  step programs' named scopes (``SCOPES``) in each op's scope path, and
  ``unscoped`` where the path holds none;
* ``spans``: per ``engine.*`` span, ``count`` (spans that start in the
  window), ``host_s`` (their seconds in the window) and ``idle_s`` (the
  device-idle seconds it is the innermost cover of).

An op's scope path is the ``tf_op`` stat of its event metadata
(``jit(_decode_impl)/while/body/attn/dot_general:``), which
``jax.profiler.ProfileData`` does not expose: ``op_paths`` reads it from
the ``XSpace`` protocol buffer's wire format, with no other package.
"""
from __future__ import annotations

import bisect
import heapq
import re
from pathlib import Path

from bench import trace

SCOPES = ("attn", "proj", "mlp", "head", "sample")
ENGINE_PREFIX = "engine."
_PROGRAM_ID = re.compile(r"\((\d+)\)$")

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_META_ID, _META_NAME, _EVENT_META_STATS = 1, 2, 5
_STAT_META_ID, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"xplane: unsupported wire type {wire}")
        yield key >> 3, value


def _map_values(entry) -> tuple[int, memoryview]:
    """(key, value) of one ``map<int64, Message>`` entry."""
    key, value = 0, memoryview(b"")
    for num, v in _fields(entry):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_paths(data: bytes) -> dict[tuple[int, str], str]:
    """``(program id, op name) -> tf_op`` of every op event's metadata on the
    device planes of one serialized ``XSpace``."""
    out: dict[tuple[int, str], str] = {}
    for num, plane in _fields(memoryview(data)):
        if num != _SPACE_PLANES:
            continue
        name, metas, stat_names = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == _PLANE_NAME:
                name = _text(v)
            elif pnum == _PLANE_EVENT_META:
                metas.append(_map_values(v)[1])
            elif pnum == _PLANE_STAT_META:
                _, meta = _map_values(v)
                sid, sname = 0, ""
                for snum, sv in _fields(meta):
                    if snum == _STAT_META_ID:
                        sid = sv
                    elif snum == _META_NAME:
                        sname = _text(sv)
                stat_names[sid] = sname
        if not trace._DEVICE.match(name):
            continue
        ids = {v: k for k, v in stat_names.items()}
        tf_op, program = ids.get("tf_op"), ids.get("program_id")
        for meta in metas:
            op, pid, path = "", 0, None
            for mnum, v in _fields(meta):
                if mnum == _META_NAME:
                    op = _text(v)
                elif mnum == _EVENT_META_STATS:
                    stat = dict(_fields(v))
                    sid = stat.get(_STAT_META_ID)
                    if sid == program:
                        pid = stat.get(_STAT_UINT, stat.get(_STAT_INT, 0))
                    elif sid == tf_op:
                        if _STAT_STR in stat:
                            path = _text(stat[_STAT_STR])
                        elif _STAT_REF in stat:
                            path = stat_names.get(stat[_STAT_REF])
            if path is not None:
                out[(pid, op)] = path
    return out


def scope_of(path: str | None) -> str:
    """The innermost of ``SCOPES`` in an op's scope path, else ``unscoped``."""
    for part in reversed(path.split("/") if path else ()):
        if part.split(":", 1)[0] in SCOPES:
            return part.split(":", 1)[0]
    return "unscoped"


def _innermost(spans: list[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
    """The timeline cut into pieces ``(start, end, name)``, each covered
    throughout by one innermost span: of the spans covering it, the one
    that started last (the shorter on a tie).  Uncovered time is left out."""
    marks = sorted({t for s, e, _ in spans for t in (s, e)})
    order = sorted(spans)
    active: list = []
    out, j = [], 0
    for a, b in zip(marks, marks[1:]):
        while j < len(order) and order[j][0] <= a:
            s, e, name = order[j]
            heapq.heappush(active, (-s, e - s, e, name))
            j += 1
        while active and active[0][2] <= a:
            heapq.heappop(active)
        if active:
            out.append((a, b, active[0][3]))
    return out


def _attribute(gaps, pieces, into: dict) -> None:
    """Add each gap's overlap with each piece to that piece's name, and the
    rest to ``host.other``; both lists are sorted and disjoint."""
    k = 0
    for gs, ge in gaps:
        while k < len(pieces) and pieces[k][1] <= gs:
            k += 1
        rest, m = ge - gs, k
        while m < len(pieces) and pieces[m][0] < ge:
            covered = min(ge, pieces[m][1]) - max(gs, pieces[m][0])
            into[pieces[m][2]] = into.get(pieces[m][2], 0.0) + covered
            rest -= covered
            m += 1
        into["host.other"] = into.get("host.other", 0.0) + max(rest, 0.0)


def _module_of(mods, starts, s: float):
    i = bisect.bisect_right(starts, s) - 1
    return mods[i][2] if i >= 0 and s < mods[i][1] else "?"


def reduce_planes(planes, paths: dict[tuple[int, str], str], top: int = 10) -> dict:
    """Reduce decoded planes (``ProfileData(...).planes``, a list) and the
    op paths of the same trace."""
    out = trace.reduce_xspace(planes, top)
    devices, spans = [], []
    for plane in planes:
        if trace._DEVICE.match(plane.name):
            devices.append({line.name: [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                            for line in plane.lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in trace.HOST_SPANS or e.name.startswith(ENGINE_PREFIX) or e.name == trace.WINDOW_SPAN:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    window = [(s, e) for s, e, name in spans if name == trace.WINDOW_SPAN]
    if window:
        lo, hi = window[0]
    else:
        ops = [(s, e) for d in devices for s, e, _ in d.get("XLA Ops", [])]
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    spans = [sp for sp in spans if sp[2] != trace.WINDOW_SPAN]
    pieces = _innermost(spans)
    gaps: dict[str, float] = {}
    scopes: dict[str, dict[str, float]] = {}
    for lines in devices:
        ops = lines.get("XLA Ops", [])
        busy = trace._union(trace._clip([(s, e) for s, e, _ in ops], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        _attribute([(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2]) if ge > gs], pieces, gaps)
        mods = sorted(lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        scoped = []
        for s, e, name in ops:
            pid = _PROGRAM_ID.search(_module_of(mods, starts, s))
            path = paths.get((int(pid.group(1)) if pid else 0, name))
            scoped.append((s, e, scope_of(path)))
        for label, d in trace._self_times(scoped, mods, lo, hi):
            module, scope = label.split("/", 1)
            per = scopes.setdefault(module, {})
            per[scope] = per.get(scope, 0.0) + d
    n = len(devices)
    engine = {}
    for s, e, name in spans:
        if name.startswith(ENGINE_PREFIX) and lo <= s < hi:
            row = engine.setdefault(name, {"count": 0, "host_s": 0.0, "idle_s": 0.0})
            row["count"] += 1
            row["host_s"] += (min(e, hi) - s) * 1e-9
    for name, row in engine.items():
        row["idle_s"] = gaps.get(name, 0.0) * 1e-9 / n
    out["idle_gaps"] = [[k, v * 1e-9 / n] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1]) if v > 0]
    out["scopes"] = {m: {k: v * 1e-9 / n for k, v in sorted(per.items(), key=lambda kv: -kv[1])}
                     for m, per in sorted(scopes.items())}
    out["spans"] = dict(sorted(engine.items()))
    return out


def reduce_file(path: Path, top: int = 10) -> dict:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(path)).planes)
    return reduce_planes(planes, op_paths(Path(path).read_bytes()), top)
