"""The correctness check's control, on the chip: for each seed, one run of a
cell with the float8 pass of the reference in the program's place.  The
check judges the gap of the tokens the control puts first, through the
same limit and ``correct`` as a benchmark run, so each row reads
``correct: false``; the served tokens' gap on the same sample is kept
beside it.

    python -m bench.control --workload deepseek-7b.docqa --seeds 11,12,13 --seconds 20

Prints one JSON line per seed, then the lower reading (the largest program
gap) and the upper reading (the smallest control gap) from which the
cell's limit in ``checks/<cell>.json`` is set.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json

from bench import registry
from bench.run import run_cell


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(registry.ROOT, args.workload, seed, args.seconds, False, control=True)
        row = {"seed": seed, "program_gap": max(r["gaps"], default=None),
               "control_gap": max(r["control_gaps"], default=None), "requests": len(r["gaps"]),
               "compared_tokens": r["compared_tokens"], "correct": r["correct"], "metrics": r["metrics"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower_reading": max(r["program_gap"] for r in rows),
                      "upper_reading": min(r["control_gap"] for r in rows)}), flush=True)


if __name__ == "__main__":
    main()
