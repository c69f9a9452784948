"""A configuration, a traffic mix, a cell and a per-layer metric are added
by new files and new entries alone: a copy of the benchmark gains one of
each, and the harness finds and runs them without an edit to its code."""
import json
import shutil
import time

import bench
from conftest import shrink


def _extend(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(bench.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    d = root / "chipbench"
    conf = bench.load_json(d / "configs" / "qwen1.5-0.5b.json")
    conf["name"] = "qwen1.5-0.5b-copy"
    (d / "configs" / "qwen1.5-0.5b-copy.json").write_text(json.dumps(conf))
    mix = dict(bench.load_json(d / "traffic" / "chat.json"), rate_per_s=3.0)
    (d / "traffic" / "burst.json").write_text(json.dumps(mix))
    cell = bench.load_json(d / "cells" / "qwen05b-chat.json")
    (d / "cells" / "copy-burst.json").write_text(json.dumps(cell))
    (d / "metrics" / "steps_seen.py").write_text(
        "def read(view):\n    return float(view.n_steps) if view.n_steps else None\n")
    spec["configs"].append({"name": "qwen1.5-0.5b-copy", "source": "x",
                            "file": "chipbench/configs/qwen1.5-0.5b-copy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "copy-burst", "config": "qwen1.5-0.5b-copy",
                              "traffic": "burst", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                              "source": "device_trace", "layer": "test",
                              "moves": "output_tok_s", "workloads": ["copy-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_files_are_found_by_name(tmp_path):
    root = _extend(tmp_path)
    cell = bench.load_cell("copy-burst", root)
    assert cell.conf["name"] == "qwen1.5-0.5b-copy"
    assert cell.mix["rate_per_s"] == 3.0
    assert cell.dir == root / "chipbench"
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and "ttft_p95_ms" not in names


def test_new_cell_runs_and_reports_its_metric(tmp_path):
    cell = shrink(bench.load_cell("copy-burst", _extend(tmp_path)))
    r = bench.run(cell, 2**31 + 5, 3.0, True, time.perf_counter(),
                  require_tpu=False, smoke=True)
    assert r["correct"] is True
    # no device plane on the CPU: the trace-backed readers find nothing
    # and the harness leaves their metrics out of the line
    assert set(r["metrics"]) <= {"steps_seen"}
    assert list(r)[-1] == "compared"
