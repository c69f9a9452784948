"""Print where a traced run's time went, by the program's own names: device
time per call of the decode and chunk steps split by program scope, device
idle time per tick split by host phase (mean, and each tick's total), and
the names of the modules and kernels that ran. Reads the newest trace of a
``--trace 1`` run; it is not a metric.

    python3 chipbench/tools/scope_split.py <cell>
"""
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flops  # noqa: E402
import scopes  # noqa: E402
import tracing  # noqa: E402
from bench import HERE, ROOT, load_cell, load_json  # noqa: E402


def main(name):
    cell = load_cell(name)
    d = ROOT / ".bench_trace" / name
    path = max(glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    steps = load_json(d / "steps.json")
    peak = load_json(HERE / "peaks.json")["TPU v5 lite"]
    v = tracing.View(tracing.load_xplane(path), steps, flops.dims_of(cell.conf), peak)
    ops = scopes.load_ops(path)
    ticks = scopes._ticks(v)
    idle = scopes.idle_ms(v) or {}
    per_tick = sorted(sum(t.values()) for t in scopes.tick_idle_ms(v) or [])
    in_tick = sum(idle.values())
    modules, unknown_in_tick = {}, 0
    for m in v.modules:
        fn = m.name.split("(")[0]
        modules[fn] = modules.get(fn, 0) + 1
        if fn == "jit__unknown" and any(t.start <= m.start <= t.end for t in ticks):
            unknown_in_tick += 1
    kernels = {}
    for o in ops:
        if tracing.is_kernel(o.name) and v.lo <= o.start <= v.hi:
            k = tracing.short_name(o.name).split(" ")[0].rsplit(".", 1)[0]
            kernels[k] = kernels.get(k, 0) + 1
    out = {
        "window_s": v.window_s, "busy_s": v.busy_s, "ticks": len(ticks),
        "modules": modules, "jit__unknown_in_ticks": unknown_in_tick,
        "kernels": kernels,
        "ops_with_a_path": sum(bool(o.path) for o in ops), "ops": len(ops),
        "decode_calls": len(v.calls("decode_step_rows")),
        "chunk_calls": len(v.calls("prefill_chunk_rows")),
        "decode_ms_by_scope": scopes.scope_ms(v, "decode_step_rows", ops),
        "chunk_ms_by_scope": scopes.scope_ms(v, "prefill_chunk_rows", ops),
        "idle_ms_per_tick_by_phase": idle,
        "idle_ms_of_each_tick_sorted": per_tick,
        "host_stall_ms_median": scopes.host_stall_ms(v),
        "idle_share_in_child_spans": (1 - idle.get("serve.tick", 0.0) / in_tick
                                      if in_tick else None),
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(*sys.argv[1:])
