"""§Perf hillclimb harness: re-lower chosen cells under candidate changes
(sharding strategy, remat policy, grad compression) and diff the roofline
terms against the baseline artifact.

  PYTHONPATH=src python -m benchmarks.perf_variants \
      --arch xlstm-1.3b --shape train_4k --mesh single \
      --variant small-repl --variant tp-ffn --remat dots
"""
from __future__ import annotations

import argparse
import json
import os

from benchmarks.common import ARTIFACTS, table


def main():
    # lower_cell asks for the 512 host devices before jax first initializes
    from repro.launch.dryrun import lower_cell
    from repro.parallel.strategies import get_strategy

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", action="append", default=[],
                    help="strategy name, or strategy:remat, or +gradcompress")
    ap.add_argument("--baseline-tag", default="baseline")
    args = ap.parse_args()

    mp = args.mesh == "multi"
    base_path = os.path.join(
        ARTIFACTS, f"{args.baseline_tag}_{args.mesh}_{args.arch}_{args.shape}.json"
    )
    rows = []

    def add(rec, label):
        rows.append([
            label, f"{rec['t_compute_s']:.3f}", f"{rec['t_memory_s']:.3f}",
            f"{rec['t_collective_s']:.3f}",
            f"{(rec['memory']['peak_bytes'] or 0)/2**30:.2f}G",
            rec.get("lower_compile_s", "-"),
        ])

    if os.path.exists(base_path):
        with open(base_path) as f:
            add(json.load(f), "baseline(artifact)")

    for v in args.variant:
        gc = v.endswith("+gradcompress")
        v2 = v.replace("+gradcompress", "")
        strat, _, remat = v2.partition(":")
        strat = strat or "baseline"
        remat = remat or "full"
        rec = lower_cell(
            args.arch, args.shape, multi_pod=mp,
            rules=get_strategy(strat), remat_policy=remat, grad_compress=gc,
            tag=f"perf-{v.replace(':', '-').replace('+', '-')}",
        )
        if rec["status"] != "OK":
            print(f"[perf] {v}: {rec['status']}")
            continue
        add(rec, v)

    print(table(rows, ["variant", "t_comp", "t_mem", "t_coll", "peak", "compile_s"]))


if __name__ == "__main__":
    main()
