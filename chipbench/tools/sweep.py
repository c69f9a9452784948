"""Capacity sweep of an open-loop cell: one process runs the cell's traffic
at each given rate and prints what the window measured. The cell's rate is
then set at about four fifths of the highest rate it sustains.

    python3 chipbench/tools/sweep.py <cell> <seconds> <seed> <rate>...
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import bench  # noqa: E402


def main(cell_name, seconds, seed, *rates):
    cell = bench.load_cell(cell_name)
    for rate in rates:
        r = bench.run(cell, int(seed), float(seconds), False, time.perf_counter(),
                      rate_per_s=float(rate))
        print(json.dumps({"rate_per_s": float(rate), "correct": r["correct"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          **{k: v["value"] for k, v in r["metrics"].items()}}),
              flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
