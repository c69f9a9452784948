"""Shared helpers of the chipbench CPU tests: the harness's modules on the
path, and cells cut to a size the CPU runs in seconds."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def tiny_cell(name: str):
    """The named cell at smoke widths (the program's ``smoke_config``), with
    short prompts and answers and a small arena."""
    import bench

    return shrink(bench.load_cell(name))


def shrink(cell):
    mix = dict(cell.mix, block=8, check_tokens=16, check_max_requests=3,
               prompt={"dist": "uniform", "min": 20, "max": 60},
               output={"dist": "uniform", "min": 4, "max": 10}, prewarm_s=0.5)
    if mix["arrivals"] == "closed":
        mix["clients"] = 3
    else:
        mix["rate_per_s"], mix["fill"] = 4.0, 3
    setup = dict(cell.setup, grace_s=30.0,
                 serving={"num_slots": 3, "page_size": 16, "max_blocks_per_slot": 8,
                          "num_pages": 25, "prefill_chunk": 32})
    cell.mix, cell.setup = mix, setup
    return cell
