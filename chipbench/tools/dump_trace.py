"""Print what a traced run's profile holds (planes, lines, the names that
take most time, a few events' stats) and save its events in the reducer's
compact form.

    python3 chipbench/tools/dump_trace.py <cell> [out.json.gz]
"""
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tracing  # noqa: E402
from bench import ROOT  # noqa: E402


def main(cell, out=None):
    path = max(glob.glob(str(ROOT / ".bench_trace" / cell / "**" / "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    print(path, os.path.getsize(path), "bytes")
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for pl in pd.planes:
        print("PLANE", pl.name)
        for ln in pl.lines:
            evs = list(ln.events)
            tot = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {ln.name!r}: {len(evs)} events; top {top}")
            if pl.name.startswith("/device:") and evs:
                for e in evs[:3]:
                    print("    e", e.name, e.start_ns, e.duration_ns,
                          {k: str(v)[:120] for k, v in dict(e.stats).items()})
    if out:
        tracing.save_events(tracing.load_xplane(path), out)
        print("saved", out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(*sys.argv[1:])
