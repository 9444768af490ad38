#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the numbers compared, on
many seeds, for sound runs of the program and for its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]
        [--runs program,control]

The control is the configuration's reference put in the program's place
one precision below the configuration's (or the program's own
lower-precision path, where it has one); each driver says which.  All
runs share one process, so set-up compiles once.  Prints one JSON line
per run and, last, the lower reading (largest of the program's) and the
upper reading (smallest of the control's) of each number.  Benchmark
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--runs", default="program,control")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness.runner import run_cell

    seeds = [int(s) for s in args.seeds.split(",")]
    readings = {}
    for kind in args.runs.split(","):
        for seed in seeds:
            t0 = time.perf_counter()
            line = run_cell(args.workload, seed=seed, seconds=args.seconds,
                            trace=False, root=ROOT,
                            variant=None if kind == "program" else kind)
            vals = {k: v["value"] for k, v in line["checks"].items()}
            print(json.dumps({"run": kind, "seed": seed,
                              "correct": line["correct"], "checks": vals,
                              "metrics": {k: v["value"] for k, v in
                                          line["metrics"].items()},
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            for k, v in vals.items():
                readings.setdefault(kind, {}).setdefault(k, []).append(v)
    summary = {}
    for k, vals in readings.get("program", {}).items():
        summary[k] = {"lower": max(vals)}
    for k, vals in readings.get("control", {}).items():
        summary.setdefault(k, {})["upper"] = min(vals)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
