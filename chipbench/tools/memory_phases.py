"""Print device 0's memory at each session boundary of one untraced run of
a cell: the ``serve.reset`` and ``serve.release`` spans' args, in order,
read from a profiler session held open over the whole run. Shows which
set-up phase first reaches the run's ``memory_peak_bytes``; not a metric.

    python3 chipbench/tools/memory_phases.py <cell> <seed> [seconds]
"""
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import bench  # noqa: E402


def main(name, seed, seconds="5"):
    import jax
    from jax.profiler import ProfileData

    t_start = time.perf_counter()
    cell = bench.load_cell(name)
    d = bench.ROOT / ".bench_trace" / f"{name}.memory"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(d), profiler_options=opts):
        result = bench.run(cell, int(seed), float(seconds), False, t_start)
    path = max(glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    spans = sorted((e.start_ns, e.name, dict(e.stats))
                   for pl in ProfileData.from_file(path).planes
                   if pl.name.startswith("/host:") for ln in pl.lines
                   for e in ln.events if e.name in ("serve.reset", "serve.release"))
    shutil.rmtree(d)      # the metric readers read the newest trace there
    t0 = spans[0][0] if spans else 0
    for t, n, stats in spans:
        print(json.dumps({"at_s": (t - t0) * 1e-9, "span": n,
                          **{k: int(v) for k, v in stats.items()}}))
    print(json.dumps({"memory_peak_bytes": result["device"]["memory_peak_bytes"],
                      "correct": result["correct"]}))


if __name__ == "__main__":
    main(*sys.argv[1:])
