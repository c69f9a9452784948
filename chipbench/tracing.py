"""Reduction of one profiler trace to per-layer numbers.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``
into flat events (plane, line, name, start, duration; ns). The device planes
are ``/device:TPU:<n>``; their ``XLA Ops`` line holds every operation that
ran, their ``XLA Modules`` line every call of a jitted program
(``jit_<function>(...)``). The host plane holds the benchmark's spans:
``bench.trace`` around the traced part of the window, ``engine.step``,
``gen.add`` and ``wait``.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

import flops as F

OPS, MODULES = "XLA Ops", "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start: float    # ns
    dur: float      # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path: str) -> list[Ev]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for pl in pd.planes:
        if not (pl.name.startswith("/device:") or pl.name.startswith("/host:")):
            continue
        for ln in pl.lines:
            for e in ln.events:
                out.append(Ev(pl.name, ln.name, e.name, float(e.start_ns),
                              float(e.duration_ns)))
    return out


def save_events(events: list[Ev], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> list[Ev]:
    with gzip.open(path, "rt") as f:
        return [Ev(*e) for e in json.load(f)]


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class View:
    """The traced window, as the metric readers see it."""

    def __init__(self, events: list[Ev], steps: list, dims: F.Dims, peak: dict):
        self.events = events
        self.dims = dims
        self.peak = peak
        span = [e for e in events if e.plane.startswith("/host:") and e.name == "bench.trace"]
        if not span:
            raise ValueError("trace holds no bench.trace span")
        self.lo, self.hi = span[0].start, span[0].end
        self.window_s = (self.hi - self.lo) * 1e-9
        self.devices = sorted({e.plane for e in events if e.plane.startswith("/device:TPU")})
        inside = lambda e: e.start >= self.lo and e.end <= self.hi
        self.ops = [e for e in events if e.line == OPS and e.plane in self.devices
                    and e.end > self.lo and e.start < self.hi]
        self.modules = [e for e in events if e.line == MODULES
                        and e.plane in self.devices and inside(e)]
        self.host = [e for e in events if e.plane.startswith("/host:")
                     and e.name in ("engine.step", "gen.add", "wait") and inside(e)]
        self.steps = steps
        self.n_steps = len(steps)
        n = max(len(self.devices), 1)
        self.busy_s = sum(union_length([(e.start, e.end) for e in self.ops
                                        if e.plane == d], self.lo, self.hi)
                          for d in self.devices) * 1e-9 / n
        self.labels = self._label_modules()
        self.note = (f"{len(self.devices)} device plane(s), {len(self.ops)} ops, "
                     f"{len(self.modules)} module calls ({len(self.labels)} named), "
                     f"{len(self.host)} host spans")

    @classmethod
    def from_dir(cls, trace_dir, tr_span, steps, dims, peak):
        paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise ValueError(f"no trace under {trace_dir}")
        return cls(load_xplane(max(paths, key=os.path.getmtime)),
                   steps[tr_span[2]:tr_span[3]], dims, peak)

    # ---------------------------------------------------------- building blocks

    def calls(self, function: str) -> list[Ev]:
        """Device executions of ``function`` in the window: named modules
        (``jit_<function>(...)``) and the engine's model steps, which jit a
        ``functools.partial`` and so reach the trace as ``jit__unknown``."""
        return [m for m, fn in self.labels if fn == function]

    def _label_modules(self) -> list[tuple[Ev, str]]:
        """Name every device module call in the window. Model steps are
        told apart by the engine tick they ran in: the modules that start
        inside an ``engine.step`` span, in order, are that tick's prompt
        chunk (if the tick ran one) and then its decode step (if any)."""
        out = []
        ticks = sorted((h for h in self.host if h.name == "engine.step"),
                       key=lambda h: h.start)
        mods = sorted(self.modules, key=lambda m: m.start)
        for m in mods:
            name = m.name.split("(")[0]
            if name != "jit__unknown":
                out.append((m, name[len("jit_"):]))
        if len(ticks) != len(self.steps):
            return out
        for t, (_, _, kv, chunk) in zip(ticks, self.steps):
            steps = [m for m in mods if m.name.startswith("jit__unknown")
                     and t.start <= m.start <= t.end]
            want = (["prefill_chunk_rows"] if chunk else []) + (
                ["decode_step_rows"] if kv else [])
            if len(steps) == len(want):
                out.extend(zip(steps, want))
        return out

    def kernel_events(self, function: str) -> list[Ev]:
        """Mosaic kernel executions (``tpu_custom_call``) inside
        ``function``'s calls."""
        spans = [(m.plane, m.start, m.end) for m in self.calls(function)]
        return [e for e in self.ops if is_kernel(e.name)
                and any(p == e.plane and s <= e.start and e.end <= t for p, s, t in spans)]

    def idle_share(self) -> float | None:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def call_ms(self, function: str) -> float | None:
        c = self.calls(function)
        return 1e-6 * sum(e.dur for e in c) / len(c) if c else None

    def tick_host_ms(self) -> float | None:
        """Mean over engine.step spans of the span's length less the device
        busy time inside it."""
        ticks = [h for h in self.host if h.name == "engine.step"]
        if not ticks or not self.devices:
            return None
        d0 = self.devices[0]
        ivs = [(e.start, e.end) for e in self.ops if e.plane == d0]
        host = [t.dur - union_length(ivs, t.start, t.end) for t in ticks]
        return 1e-6 * sum(host) / len(host)

    def decode_roofline(self) -> float | None:
        kt = sum(e.dur for e in self.kernel_events("decode_step_rows")) * 1e-9
        need = sum(F.min_time(*F.decode_attn(self.dims, kv), self.peak["flops_bf16"],
                              self.peak["hbm_bytes_per_s"])[0]
                   for _, _, kv, _ in self.steps if kv)
        return 100.0 * need / kt if kt > 0 and need > 0 else None

    def prefill_roofline(self) -> float | None:
        kt = sum(e.dur for e in self.kernel_events("prefill_chunk_rows")) * 1e-9
        need = sum(F.min_time(*F.chunk_attn(self.dims, c[0], c[1]),
                              self.peak["flops_bf16"], self.peak["hbm_bytes_per_s"])[0]
                   for _, _, _, c in self.steps if c)
        return 100.0 * need / kt if kt > 0 and need > 0 else None

    def mfu(self) -> float | None:
        """Model FLOPs the traced steps needed over window x peak."""
        total = 0.0
        for _, _, kv, c in self.steps:
            tokens = len(kv) + (c[1] if c else 0)
            sampled = len(kv) + (1 if c and c[2] else 0)
            total += F.model_flops(self.dims, tokens, sampled)
            if kv:
                total += F.decode_attn(self.dims, kv)[0]
            if c:
                total += F.chunk_attn(self.dims, c[0], c[1])[0]
        if total <= 0 or self.window_s <= 0:
            return None
        return 100.0 * total / (self.window_s * self.peak["flops_bf16"])

    def breakdown(self) -> dict:
        by = {}
        for e in self.ops:
            k = short_name(e.name)
            by[k] = by.get(k, 0.0) + e.dur * 1e-9
        top = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        idle = []
        if self.devices:
            d0 = self.devices[0]
            for s, t in gaps([(e.start, e.end) for e in self.ops if e.plane == d0],
                             self.lo, self.hi):
                mid = (s + t) / 2
                who = [h.name for h in self.host if h.start <= mid <= h.end]
                idle.append([who[-1] if who else "other", (t - s) * 1e-9])
        idle.sort(key=lambda x: -x[1])
        return {"device_ops": [[n, v] for n, v in top], "idle_gaps": idle[:10]}


def is_kernel(name: str) -> bool:
    """A Mosaic kernel's op in the trace: an HLO custom call to the TPU
    custom-call target (not, say, an ``AllocateBuffer`` custom call)."""
    return 'custom_call_target="tpu_custom_call"' in name


def short_name(name: str) -> str:
    """``%copy.62 = bf16[...] copy(...)`` -> ``copy.62 copy``; the XLA Ops
    line names each op by its whole HLO text."""
    lhs, _, rhs = name.partition(" = ")
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + rhs)
    op = m.group(1) if m else ""
    if is_kernel(name):
        op = "tpu_custom_call"
    return f"{lhs.lstrip('%')} {op}".strip()
