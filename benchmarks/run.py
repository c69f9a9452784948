"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import sys
import traceback


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.2f},{derived}")


def main() -> None:
    from benchmarks import (bench_cpq, bench_decomposition, bench_e2e_energy,
                            bench_pipeline, bench_retrieval, roofline)

    modules = [
        ("bench_decomposition", bench_decomposition),   # paper §III / Fig. 2
        ("bench_pipeline", bench_pipeline),             # paper Fig. 3
        ("bench_cpq", bench_cpq),                       # paper §IV Fig. 4-5
        ("bench_retrieval", bench_retrieval),           # paper §V
        ("bench_e2e_energy", bench_e2e_energy),         # paper §IV table
        ("roofline", roofline),                         # deliverable (g)
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules:
        try:
            mod.main(emit)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        sys.exit(f"benchmark modules failed: {failed}")


if __name__ == "__main__":
    main()
