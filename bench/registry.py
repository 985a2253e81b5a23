"""Find a cell's pieces by name: configuration, traffic mix, metric readers,
and the per-family work and reference modules.

Every lookup is relative to a benchmark root (the checkout), so a copy of
the benchmark with extra files finds them without an edit.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parent.parent


def bench_dir(root: Path) -> Path:
    return Path(root) / "bench"


def load_benchmark(root: Path = ROOT) -> dict:
    """``BENCHMARK.json`` at ``root``."""
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                     f"(cells: {', '.join(c['name'] for c in bench['workloads'])})")


def _json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SystemExit(f"bench: no {what} file {path}")
    return json.loads(path.read_text())


def config_file(root: Path, name: str) -> dict:
    return _json(bench_dir(root) / "configs" / f"{name}.json", "configuration")


def mix_file(root: Path, name: str) -> dict:
    return _json(bench_dir(root) / "traffic" / f"{name}.json", "traffic mix")


def load_module(path: Path, name: str) -> ModuleType:
    """Import one file as a module of its own (metric names may hold ``.``
    and ``-``, which a package import cannot)."""
    if not path.is_file():
        raise SystemExit(f"bench: no module file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_file_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, name: str) -> ModuleType:
    """``metrics/<name>.py``; its ``read(ctx)`` returns a number or None."""
    return load_module(bench_dir(root) / "metrics" / f"{name}.py", name)


def family_module(root: Path, kind: str, family: str) -> ModuleType:
    """``work/<family>.py`` or ``reference/<family>.py``."""
    return load_module(bench_dir(root) / kind / f"{family}.py", f"{kind}_{family}")


def peaks(root: Path, device_kind: str) -> dict:
    """Peak rates of ``device_kind``; a kind not in the table is an error."""
    table = _json(bench_dir(root) / "peaks.json", "peaks")
    if device_kind not in table["devices"]:
        raise KeyError(f"bench: no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics a cell reports: those that list
    the cell under ``workloads``, or list none."""
    def applies(m: dict) -> bool:
        return "workloads" not in m or cell in m["workloads"]

    return ([m for m in bench["end_to_end"] if applies(m)],
            [m for m in bench["per_layer"] if applies(m)])
