"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Artifacts land in experiments/bench/<name>.json.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.runtime import enable_compile_cache

from . import (
    bench_ablation,
    bench_accuracy_sparsity,
    bench_comparison,
    bench_dataflows,
    bench_hardware,
    bench_prune_throughput,
    bench_roofline,
    bench_serve_continuous,
    bench_sparsity_effect,
    bench_stalls,
    bench_utilization,
)

BENCHES = {
    "accuracy_sparsity": bench_accuracy_sparsity.run,  # Figs. 11/12/14
    "prune_throughput": bench_prune_throughput.run,  # Fig. 13
    "dataflows": bench_dataflows.run,  # Fig. 15
    "stalls": bench_stalls.run,  # Fig. 16
    "utilization": bench_utilization.run,  # Fig. 17
    "hardware": bench_hardware.run,  # Table III / Fig. 18
    "sparsity_effect": bench_sparsity_effect.run,  # Fig. 19
    "comparison": bench_comparison.run,  # Fig. 20
    "ablation": bench_ablation.run,  # Table IV
    "roofline": bench_roofline.run,  # §Roofline (from dry-run artifacts)
    "serve_continuous": bench_serve_continuous.run,  # paged-KV continuous batching
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced repeats/steps")
    ap.add_argument("--only", default=None, help="run one benchmark by name")
    args = ap.parse_args()
    enable_compile_cache()

    names = [args.only] if args.only else list(BENCHES)
    failures = []
    for name in names:
        t0 = time.time()
        try:
            BENCHES[name](quick=args.quick)
            print(f"[run] {name} done in {time.time()-t0:.1f}s")
        except Exception:
            failures.append(name)
            print(f"[run] {name} FAILED:\n{traceback.format_exc()}")
    if failures:
        print(f"[run] FAILURES: {failures}")
        sys.exit(1)
    print(f"[run] all {len(names)} benchmarks passed")


if __name__ == "__main__":
    main()
