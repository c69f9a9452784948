"""Program scopes and host phases in the traced window.

The program names its own work (docs/serving.md, Observability):

- every device op carries the ``jax.named_scope`` path it was traced
  under, e.g. ``jit(decode_step_rows)/layers/while/body/attn.kernel/...``.
  The profiler keeps it as the ``tf_op`` stat of the op's event metadata,
  which neither ``tracing.py`` nor ``jax.profiler.ProfileData`` reads, so
  this module reads the View's trace again (the newest ``.xplane.pb`` under
  ``.bench_trace/`` whose device ops are the View's) and keeps it. An op's
  *scope* is the innermost name of ``SCOPES`` in that path; ops whose scope
  is ``layers`` itself are all the layer scan's own unscoped ops: the
  stacked arena and weight slices in, the arena written back, the layout
  copies the compiler adds for them, and a non-attention mixer's work;
- the engine's tick is a ``serve.tick`` span on the host plane, with one
  child span per host phase (``PHASES``).

A program without these names (an older commit) gives no scope and no
tick: the readers then return None.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import statistics

import tracing as T

SCOPES = ("embed", "layers", "attn.qkv", "kv_write", "attn.kernel",
          "attn.out", "mlp", "norm", "lm_head")
PHASES = ("serve.admit", "serve.prefill_chunk", "serve.grow",
          "serve.speculate", "serve.decode", "serve.sample", "serve.emit")
CONTROL = ("while", "conditional", "call")     # ops that hold other ops
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          ".bench_trace")


@dataclasses.dataclass(frozen=True)
class Op:
    plane: str
    start: float    # ns
    dur: float      # ns
    name: str       # the op's HLO text, as the trace's XLA Ops line has it
    path: str       # its tf_op: the named-scope path it was traced under

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def scope(self) -> str:
        return innermost(self.path)

    @property
    def control(self) -> bool:
        return T.short_name(self.name).split(" ")[-1] in CONTROL


def innermost(path: str) -> str:
    """The innermost program scope in an op's ``tf_op`` path, or "" for
    none. The profiler ends the path with ``:<op type>``, and joins the
    paths of a fusion's ops with ``;``: the first path names the fusion."""
    head = path.split(";")[0].rsplit(":", 1)[0]
    names = [p for p in head.split("/") if p in SCOPES]
    return names[-1] if names else ""


# ProfileData does not expose the stats of an event's metadata, where the
# profiler keeps ``tf_op``; so the XSpace protobuf is read here, for its
# device planes' event metadata only. Field numbers are those of
# tsl/profiler/protobuf/xplane.proto.

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of each field of one protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _stat(b, names: dict) -> tuple[str, object]:
    f = dict(_fields(b))
    if 5 in f:
        v = bytes(f[5]).decode(errors="replace")
    elif 7 in f:                                 # interned string
        v = names.get(f[7], "")
    else:
        v = f.get(4, f.get(3))
    return names.get(f.get(1, 0), ""), v


def metadata_stats(path: str) -> dict[tuple[str, str], dict]:
    """(device plane, event name) -> the stats of that event's metadata."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for fno, plane in _fields(space):
        if fno != 1:
            continue
        fields = list(_fields(plane))
        pname = next((bytes(v).decode() for f, v in fields if f == 2), "")
        if not pname.startswith("/device:"):
            continue
        names = {}
        for f, entry in fields:                  # stat_metadata map entries
            if f == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for f, entry in fields:                  # event_metadata map entries
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            stats = dict(_stat(v, names) for g, v in meta if g == 5)
            for g, v in meta:                    # name, display_name
                if stats and g in (2, 4) and len(v):
                    out[(pname, bytes(v).decode())] = stats
    return out


def load_ops(path: str) -> list[Op]:
    """The device ops of a ``.xplane.pb`` with their ``tf_op`` paths."""
    from jax.profiler import ProfileData

    meta = metadata_stats(path)
    out = []
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/device:"):
            continue
        for ln in pl.lines:
            if ln.name != T.OPS:
                continue
            for e in ln.events:
                tf_op = meta.get((pl.name, e.name), {}).get("tf_op", "")
                out.append(Op(pl.name, float(e.start_ns), float(e.duration_ns),
                              e.name, str(tf_op)))
    return out


def _key(e) -> tuple:
    return (e.plane, e.start, e.dur, e.name)


def trace_ops(view, root: str = TRACE_ROOT) -> list[Op]:
    """The ops of the trace the View was built from: the newest trace under
    ``root`` whose device ops in the window are the View's, or none."""
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    want = {_key(e) for e in view.ops}
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        ops = load_ops(path)
        if {_key(o) for o in ops if o.plane in view.devices
                and o.end > view.lo and o.start < view.hi} == want:
            return ops
    return []


def scope_ms(view, function: str, ops: list[Op] | None = None) -> dict[str, float]:
    """Device time per call of ``function``, split by the innermost scope
    of each op inside the call ("" for ops that carry none). Ops that hold
    other ops (the layer scan's ``while``) are left out: their time is
    their contents'."""
    calls = view.calls(function)
    if not calls:
        return {}
    ops = trace_ops(view) if ops is None else ops
    by_plane: dict[str, list[Op]] = {}
    for o in ops:
        if not o.control:
            by_plane.setdefault(o.plane, []).append(o)
    split: dict[str, float] = {}
    for c in calls:
        for o in by_plane.get(c.plane, ()):
            if c.start <= o.start and o.end <= c.end:
                split[o.scope] = split.get(o.scope, 0.0) + o.dur
    return {k: 1e-6 * v / len(calls) for k, v in split.items()}


def arena_move_ms(view, ops: list[Op] | None = None) -> float | None:
    """Device time per decode call of the ops whose innermost scope is
    ``layers``: all the layer scan's own unscoped ops (the stacked arena
    and weight slices, the arena written back, their layout copies)."""
    split = scope_ms(view, "decode_step_rows", ops)
    return split.get("layers") if split else None


def _ticks(view) -> list[T.Ev]:
    return [e for e in view.events if e.plane.startswith("/host:")
            and e.name == "serve.tick" and e.start >= view.lo and e.end <= view.hi]


def tick_idle_ms(view) -> list[dict[str, float]] | None:
    """Device-0 idle time inside each ``serve.tick`` span of the window, ms,
    split by the child span the host was in (``"serve.tick"`` for none)."""
    ticks = _ticks(view)
    if not ticks or not view.devices:
        return None
    d0 = view.devices[0]
    busy = [(e.start, e.end) for e in view.ops if e.plane == d0]
    kids = sorted((e for e in view.events if e.plane.startswith("/host:")
                   and e.name in PHASES and view.lo <= e.start and e.end <= view.hi),
                  key=lambda e: e.start)
    out = []
    for t in ticks:
        split: dict[str, float] = {}
        inner = [k for k in kids if t.start <= k.start and k.end <= t.end]
        near = [(s, e) for s, e in busy if e > t.start and s < t.end]
        for s, e in T.gaps(near, t.start, t.end):
            cut = [(max(s, k.start), min(e, k.end), k.name) for k in inner]
            cut = [(a, b, n) for a, b, n in cut if b > a]
            for a, b, n in cut:
                split[n] = split.get(n, 0.0) + 1e-6 * (b - a)
            rest = (e - s) - sum(b - a for a, b, _ in cut)
            if rest > 0:
                split["serve.tick"] = split.get("serve.tick", 0.0) + 1e-6 * rest
        out.append(split)
    return out


def idle_ms(view) -> dict[str, float] | None:
    """Mean device-0 idle time per tick, ms, split by child span."""
    ticks = tick_idle_ms(view)
    if ticks is None:
        return None
    split: dict[str, float] = {}
    for t in ticks:
        for k, v in t.items():
            split[k] = split.get(k, 0.0) + v / len(ticks)
    return split


def host_stall_ms(view) -> float | None:
    """Median over the window's ``serve.tick`` spans of the device-0 idle
    time inside each, ms. A median: one long readback wait with the device
    idle would lift a mean many-fold."""
    ticks = tick_idle_ms(view)
    return statistics.median(sum(t.values()) for t in ticks) if ticks else None
