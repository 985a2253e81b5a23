"""Shared set-up of the benchmark's CPU tests: the checkout on the path, and
a copy of the benchmark with smoke-size cells beside the program."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

SMOKE_KEYS = ["num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
              "intermediate_size", "vocab_size"]
# smoke-size limit of the served gap, set from CPU readings over 7 seeds per
# cell: sound runs read 0.0-0.031, the float8 control 0.22-0.59
SMOKE_LIMIT = 0.08


def _smoke_config(src: dict, name: str, dims: dict, deployment: dict) -> dict:
    cfg = dict(src, name=name, reduced=SMOKE_KEYS, deployment=deployment)
    cfg.update(dims)
    return cfg


def make_smoke_root(dst: Path) -> Path:
    """A checkout holding a copy of ``bench/``, the program (linked) and a
    ``BENCHMARK.json`` whose two cells are smoke-size twins of the real
    ones, each added as files of its own."""
    shutil.copytree(REPO / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfgs = dst / "bench" / "configs"
    qwen = _smoke_config(json.loads((cfgs / "qwen3-4b.json").read_text()), "qwen3-smoke",
                         dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
                              head_dim=16, intermediate_size=128, vocab_size=512),
                         {"chips": 1, "slots": 4, "max_len": 128, "num_pages": 40})
    deep = _smoke_config(json.loads((cfgs / "deepseek-7b.json").read_text()), "deepseek-smoke",
                         dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                              head_dim=16, intermediate_size=128, vocab_size=512),
                         {"chips": 1, "slots": 4, "max_len": 192, "num_pages": 48})
    for cfg in (qwen, deep):
        (cfgs / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    mixes = dst / "bench" / "traffic"
    chat = json.loads((mixes / "chat.json").read_text())
    chat.update(stagger_ticks=1, lead_in_ticks=4, deck=8,
                prompt={"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 8, "max": 64},
                output={"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 4, "max": 32})
    (mixes / "tinychat.json").write_text(json.dumps(chat))
    docqa = json.loads((mixes / "docqa.json").read_text())
    docqa.update(stagger_ticks=2, lead_in_ticks=4, deck=8,
                 documents={"count": 3, "dist": "lognormal", "median": 48, "sigma": 0.4, "min": 32, "max": 80},
                 prompt={"dist": "uniform", "min": 4, "max": 12},
                 output={"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 4, "max": 32})
    (mixes / "tinydocqa.json").write_text(json.dumps(docqa))
    bench["configs"] = [{"name": c["name"], "source": c["source"], "file": f"bench/configs/{c['name']}.json",
                         "reduced": SMOKE_KEYS, "why": "smoke twin"} for c in (qwen, deep)]
    bench["workloads"] = [
        {"name": "qwen3-smoke.chat", "config": "qwen3-smoke", "traffic": "tinychat", "chips": 1, "why": "smoke"},
        {"name": "deepseek-smoke.docqa", "config": "deepseek-smoke", "traffic": "tinydocqa", "chips": 1,
         "why": "smoke"},
    ]
    twins = {"qwen3-4b.chat": "qwen3-smoke.chat", "deepseek-7b.docqa": "deepseek-smoke.docqa"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [twins[w] for w in m["workloads"]]
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    for cell in ("qwen3-smoke.chat", "deepseek-smoke.docqa"):
        (dst / "bench" / "checks" / f"{cell}.json").write_text(json.dumps({"max_logit_gap": {"limit": SMOKE_LIMIT}}))
    return dst


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory) -> Path:
    return make_smoke_root(tmp_path_factory.mktemp("bench"))
