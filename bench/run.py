#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for.  The cell is an entry of ``workloads`` in ``BENCHMARK.json``;
everything it needs is found by name (see ``harness/spec.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The numbers compared to decide ``correct`` are printed beside
their limits as the last lines on standard error and under ``checks``.

Exits 1 without a result line where JAX finds no TPU or too few chips,
and 2 where the checkout holds no program (``src/repro``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402

# the TPU runtime would otherwise log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: no program under src/repro in this checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness.device import NoAccelerator
    from harness.runner import run_cell

    try:
        line = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START, root=ROOT)
    except NoAccelerator as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
