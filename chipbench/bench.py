"""One run of one cell: set-up, the measured window, the check of what the
window served, and the result line.

Everything that belongs to a configuration, a traffic mix, a cell or a
per-layer metric is read from its own file, found by the names in
``BENCHMARK.json``:

- ``configs/<config>.json``: published keys, the program's arch id and
  overrides, the reference module (``configs/<reference>.py``);
- ``traffic/<mix>.json``: the generator's parameters;
- ``cells/<workload>.json``: attention mode, serving settings, the limit of
  the correctness comparison;
- ``metrics/<metric>.py``: a reader over the traced window (``read(view)``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


# ------------------------------------------------------------------ the spec


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict          # configs/<config>.json
    mix: dict           # traffic/<mix>.json
    setup: dict         # cells/<workload>.json
    end_to_end: list    # BENCHMARK.json metrics that apply to this cell
    per_layer: list
    dir: Path           # the benchmark's directory the files came from


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    (w,) = [w for w in spec["workloads"] if w["name"] == name] or [None]
    if w is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    (c,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    d = root / spec["paths"][0]
    return Cell(
        name=name, chips=w["chips"], conf=load_json(root / c["file"]),
        mix=load_json(d / "traffic" / f"{w['traffic']}.json"),
        setup=load_json(d / "cells" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)], dir=d)


# ------------------------------------------------------------- the program


def program_config(conf: dict, smoke=None):
    """The program's ModelConfig for a configuration file: its arch id with
    the file's overrides, checked against the file's published widths.
    ``smoke`` (CPU tests only): True for the program's ``smoke_config``, or
    a dict of ModelConfig fields that cut the widths."""
    from repro.configs import get_config, smoke_config

    p = conf["program"]
    cfg = dataclasses.replace(get_config(p["arch"]), **p.get("overrides", {}))
    from flops import dims_of
    dims = dims_of(conf)
    got = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
           cfg.d_ff, cfg.vocab_size, cfg.num_layers)
    want = (dims.d, dims.heads, dims.kv_heads, dims.head_dim, dims.ff,
            dims.vocab, dims.layers)
    if got != want:
        raise SystemExit(f"program config {got} != configuration file {want}")
    if isinstance(smoke, dict):
        return dataclasses.replace(cfg, **smoke)
    return smoke_config(cfg) if smoke else cfg


def smoke_conf(conf: dict, cfg) -> dict:
    """Published keys rewritten to the program's smoke widths (CPU tests)."""
    out = dict(conf)
    out.update(hidden_size=cfg.d_model, num_attention_heads=cfg.num_heads,
               num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
               vocab_size=cfg.vocab_size, num_hidden_layers=cfg.num_layers)
    out["intermediate_size" if "intermediate_size" in conf else "ffn_dim"] = cfg.d_ff
    return out


# ---------------------------------------------------------------- counting


class CompileCounter:
    """Counts tracing, lowering and compilation events (also persistent
    cache loads) while ``armed``; the window should see none."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.n = 0
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._hit)

    def close(self):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)
        mon.unregister_event_listener(self._hit)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.n += 1

    def _hit(self, event, **kw):
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.n += 1


# ------------------------------------------------------------------- the run


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_tpu: bool = True, smoke=None, fault=None,
        rate_per_s: float | None = None, precision: str = "f32") -> dict:
    """One run of ``cell``. Returns the result object (the last line).
    ``smoke``/``require_tpu=False`` are for CPU tests only; ``fault`` plants
    a fault in the timed path (tests of the comparison); ``rate_per_s``
    overrides an open-loop mix's rate (the capacity sweep);
    ``precision="fp8"`` runs the control (``tools/limits.py``): the
    comparison then judges the tokens the fp8 reference puts first, and
    the program's own widest gap is returned as ``program_gap``."""
    import jax
    import numpy as np

    cache_dir = ROOT / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devs = jax.devices()
    peaks = load_json(cell.dir / "peaks.json")
    if require_tpu:
        if devs[0].platform != "tpu" or len(devs) < cell.chips:
            raise SystemExit(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                             f"JAX found {len(devs)} {devs[0].platform} device(s)")
        if devs[0].device_kind not in peaks:
            raise SystemExit(f"no peaks for device kind {devs[0].device_kind!r}")
    peak = peaks.get(devs[0].device_kind)

    from repro.models import model as M
    from repro.serving.engine import ContinuousServeEngine
    from repro.serving.request import BATCH, SamplingParams, ServeRequest
    from repro.configs.base import ServingCfg

    import gen
    import stats
    import flops
    import weights

    cfg = program_config(cell.conf, smoke)
    conf = smoke_conf(cell.conf, cfg) if smoke else cell.conf
    dims = flops.dims_of(conf)
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, mode=cell.setup["mode"]))
    serving = ServingCfg(**cell.setup["serving"])
    counter = CompileCounter()

    # ---- set-up: weights, engine, every shape of the window, the fill
    phases = {"start": time.perf_counter() - t_start}
    params = weights.make_params(M.abstract_params(cfg), seed)
    jax.block_until_ready(params)
    phases["weights"] = time.perf_counter() - t_start
    eng = ContinuousServeEngine(cfg, params, serving=serving)
    if fault is not None:
        fault(eng)
    traffic = gen.Traffic(cell.mix, seed, cfg.vocab_size, serving.max_len)

    def request(r: gen.Req, max_tokens: int | None = None) -> ServeRequest:
        return ServeRequest(
            prompt=r.prompt, slo=BATCH,
            sampling=SamplingParams(max_tokens=max_tokens or r.max_tokens))

    # warm-up: one request long enough for a first and a later chunk, and
    # two decode steps, then a fresh session (new arenas, no requests); the
    # old session is released first, or both sessions' arenas coexist
    C = serving.prefill_chunk
    warm = np.arange(C + 1, dtype=np.int32) % cfg.vocab_size
    eng.add_request(ServeRequest(prompt=warm, slo=BATCH,
                                 sampling=SamplingParams(max_tokens=3)))
    while eng.has_unfinished():
        eng.step()
    eng.release()
    eng.reset()
    phases["warm"] = time.perf_counter() - t_start

    tracks: dict[int, stats.Track] = {}
    reqs: dict[int, gen.Req] = {}
    steps: list[tuple] = []          # (t0, t1, decode kv lens, chunk)
    prefilled: dict[int, int] = {}
    pending: list[tuple[float, gen.Req, int | None]] = []   # due, req, cap

    def add_due(now: float):
        while pending and pending[0][0] <= now:
            due, r, cap = pending.pop(0)
            with jax.profiler.TraceAnnotation("gen.add"):
                rid = eng.add_request(request(r, cap))
            reqs[rid] = r
            tracks[rid] = stats.Track(due=due, added=time.perf_counter())
            prefilled[rid] = 0

    counts = [0, 0]     # prefill chunks and tokens so far (eng.stats())

    def step():
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.step"):
            outs = eng.step()
        b = time.perf_counter()
        kv, chunk, finished = [], None, []
        for ev in outs:
            if ev.token < 0:
                continue
            tr = tracks[ev.rid]
            tr.times.append(b)
            tr.tokens.append(ev.token)
            if ev.index >= 1:
                kv.append(len(reqs[ev.rid].prompt) + ev.index)
            if ev.finished:
                tr.finished_at = b
                finished.append(ev.rid)
        s = eng.stats()
        if s["prefill_chunks"] > counts[0]:
            valid = s["prefill_tokens"] - counts[1]
            # the engine streams the oldest admitted prompt first: the chunk
            # is the one whose first token came now, else the oldest request
            # still waiting for its first token
            first = [ev.rid for ev in outs if ev.index == 0 and ev.token >= 0]
            rid = first[0] if first else next(r for r, tr in tracks.items()
                                              if not tr.times)
            chunk = (prefilled[rid], valid, bool(first))
            prefilled[rid] += valid
        counts[:] = s["prefill_chunks"], s["prefill_tokens"]
        steps.append((a, b, kv, chunk))
        return finished

    # the fill: the requests in flight when the window opens. Each starts
    # mid-request (gen.first_residual), its prompt prefilled in set-up; a
    # closed loop's clients then send their next request on each finish
    closed = cell.mix["arrivals"] == "closed"
    n = cell.mix["clients"] if closed else cell.mix.get("fill", 0)
    now = time.perf_counter()
    for k in range(n):
        r = traffic.next_request()
        pending.append((now, r, gen.first_residual(r.max_tokens, k, n)))
    add_due(now)
    while any(not tracks[rid].times for rid in tracks):
        for rid in step():
            if closed:
                pending.append((time.perf_counter(), traffic.next_request(), None))
        add_due(time.perf_counter())
    t0 = time.perf_counter()
    if not closed:
        # open loop: arrivals start prewarm_s before the window
        rate = rate_per_s or cell.mix["rate_per_s"]
        due = t0
        t0 += cell.mix.get("prewarm_s", 0.0)
        while due < t0 + seconds:
            pending.append((due, traffic.next_request(), None))
            due += traffic.next_gap(rate)
    phases["fill"] = time.perf_counter() - t_start
    setup_s = None
    t1 = t0 + seconds
    tr_span = None
    trace_dir = ROOT / ".bench_trace" / cell.name
    trace_s = min(cell.mix.get("trace_seconds", 3.0), seconds)
    tr_at = t0 + (seconds - trace_s) / 2
    tracing = None
    lateness = []
    while True:
        now = time.perf_counter()
        if now >= t1:
            break
        if setup_s is None and now >= t0:
            setup_s = now - t_start
            counter.armed = True
        if trace and tracing is None and now >= tr_at:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracing = jax.profiler.TraceAnnotation("bench.trace")
            tracing.__enter__()
            tr_span = [time.perf_counter(), None, len(steps)]
        if tracing is not None and tr_span[1] is None and now >= tr_at + trace_s:
            tracing.__exit__(None, None, None)
            tr_span[1] = time.perf_counter()
            tr_span.append(len(steps))
            jax.profiler.stop_trace()
        n_before = len(tracks)
        add_due(now)
        lateness.extend(tr.added - tr.due for tr in list(tracks.values())[n_before:])
        if eng.has_unfinished():
            for rid in step():
                if closed:
                    pending.append((time.perf_counter(), traffic.next_request(), None))
        else:
            nxt = pending[0][0] if pending else t1
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(max(0.0, min(nxt, t1) - time.perf_counter()))
    counter.armed = False
    counter.close()
    if setup_s is None:
        setup_s = t0 - t_start
    if tracing is not None and tr_span[1] is None:
        tracing.__exit__(None, None, None)
        tr_span[1] = time.perf_counter()
        tr_span.append(len(steps))
        jax.profiler.stop_trace()

    # ---- after the close: follow requests due in the window to their
    # first token (no new arrivals), for at most grace_s
    grace = cell.setup.get("grace_s", 60.0)
    waiting = [rid for rid, tr in tracks.items() if t0 <= tr.due < t1 and not tr.times]
    t_g = time.perf_counter()
    while waiting and eng.has_unfinished() and time.perf_counter() - t_g < grace:
        step()
        waiting = [rid for rid in waiting if not tracks[rid].times]

    phases["grace"] = time.perf_counter() - t_start
    e2e = stats.window_metrics(tracks.values(), t0, t1)
    n_steps_win = sum(1 for s in steps if t0 <= s[0] < t1)
    mem = devs[0].memory_stats() or {}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell.chips])
    log(f"window: {seconds:.1f}s, steps {n_steps_win}, due {e2e['attempted']}, "
        f"failed {e2e['failed']}, compiles in window {counter.n}, "
        f"generator lateness p95 "
        f"{1e3 * stats.percentile(lateness, 95) if lateness else 0.0:.3f} ms, "
        f"max {1e3 * max(lateness, default=0.0):.3f} ms, "
        f"nonfinite rows {eng.stats()['nonfinite_logit_rows']}, "
        f"bytes in use {mem.get('bytes_in_use', 0)}")
    t_close = t1

    # ---- correctness: reference over a seeded sample of finished requests
    finished = [rid for rid, tr in tracks.items() if tr.finished_at <= t_close]
    nonfinite = eng.stats()["nonfinite_logit_rows"]
    eng.release()
    del eng
    import correct
    check = correct.check(cell, conf, params, reqs, tracks, finished, seed,
                          serving.max_len, precision)
    check["compared"].append(["nonfinite_logit_rows", nonfinite, 0])
    check["ok"] = check["ok"] and nonfinite == 0
    del params
    phases["check"] = time.perf_counter() - t_start
    log("phases (s since process start): " + ", ".join(
        f"{k} {v:.2f}" for k, v in phases.items()))

    ok = check["ok"] and counter.n == 0
    result = {"correct": bool(ok), "attempted": e2e["attempted"],
              "failed": e2e["failed"] + check["wrong_requests"]}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": cell.chips, "memory_peak_bytes": int(mem_peak)}
    metrics = {}
    if not trace:
        vals = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
        result.update(metrics=metrics, device=dev)
    else:
        import tracing as trc
        with open(trace_dir / "steps.json", "w") as f:
            json.dump(steps[tr_span[2]:tr_span[3]], f)
        view = trc.View.from_dir(trace_dir, tr_span, steps, dims, peak)
        for m in cell.per_layer:
            reader = load_module(cell.dir / "metrics" / f"{m['name']}.py")
            v = reader.read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy_s=view.busy_s, window_s=view.window_s)
        result.update(metrics=metrics, device=dev, breakdown=view.breakdown())
        log(f"trace: window {view.window_s:.4f}s, busy {view.busy_s:.4f}s, "
            f"steps {view.n_steps}, {view.note}")
    compared = check["compared"] + [["compiles_in_window", counter.n, 0]]
    for name, value, limit in compared:
        log(f"check {name}: {value} (limit {limit})")
    if "program_gap" in check:
        result["program_gap"] = check["program_gap"]
    result["compared"] = {n: {"value": v, "limit": l} for n, v, l in compared}
    return result
