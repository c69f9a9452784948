"""Readings for the limit of the correctness comparison: runs a cell on
several seeds in one process with the fp8 control in the comparison, and
prints, per seed, the program's widest gap, the control's (the reference
in fp8 on the same prompts and served tokens) and whether the run came
out correct: at the cell's limit the control has to make it false.

    python3 chipbench/tools/limits.py <cell> <seconds> <seed>...
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import bench  # noqa: E402


def main(cell_name, seconds, *seeds):
    cell = bench.load_cell(cell_name)
    for seed in seeds:
        r = bench.run(cell, int(seed), float(seconds), False, time.perf_counter(),
                      precision="fp8")
        print(json.dumps({"seed": int(seed), "correct": r["correct"],
                          "program_gap": r["program_gap"],
                          "control_gap": r["compared"]["max_logit_gap"]["value"],
                          "tokens": r["compared"]["checked_tokens"]["value"],
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
              flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
