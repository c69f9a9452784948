"""The readers of the program's own names (scopes.py): device time per
program scope, and device idle time per host phase of an engine tick, on a
hand-made trace whose answers are known."""
import pytest

import bench
import flops as F
import scopes as S
import tracing as T

PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
DEV, HOST = "/device:TPU:0", "/host:CPU"
DIMS = F.Dims(d=1024, heads=16, kv_heads=16, head_dim=64, ff=2816,
              vocab=151936, layers=24, gated_mlp=True)
MS = 1e6
WHILE = "%while.5 = (s32[]) while(%tuple.1), condition=%cond.1, body=%body.1"
KERNEL = ('%paged_flash_decode.9 = bf16[8,16,1,64] custom-call(%copy-done), '
          'custom_call_target="tpu_custom_call"')
DEC = "jit(decode_step_rows)/layers/while/body"


def _trace(named=True, stall_ms=0.0):
    """Four 25 ms ticks; ticks 0 and 2 also run a prompt chunk (ms from the
    tick's start):

        host   admit 0-1 | chunk 1-3 | grow 3-3.5 | decode 3.5-4 | sample 4-20 | emit 20-22
        device chunk 2-8 (layers 2-4, kernel 4-8); decode 8-18 (while 8-18:
               layers 8-14, kernel 14-17, unscoped 17-18)

    and ticks 1 and 3 none (admit 0-1 | grow 1-1.5 | decode 1.5-2 |
    sample 2-20 | emit 20-22; decode 3-13: layers 3-9, kernel 9-12,
    unscoped 12-13). ``named=False`` is the same trace from a program
    without the names: no serve spans, ``jit__unknown`` modules, no op
    paths. ``stall_ms`` lengthens tick 3's readback wait (its sample span),
    the device idle throughout."""
    ev = [T.Ev(HOST, "python3", "bench.trace", 0, (100 + stall_ms) * MS)]
    ops, steps = [], []

    def op(name, path, a, b):
        ev.append(T.Ev(DEV, T.OPS, name, s + a * MS, (b - a) * MS))
        ops.append(S.Op(DEV, s + a * MS, (b - a) * MS, name, path if named else ""))

    def span(name, a, b):
        if named:
            ev.append(T.Ev(HOST, "python3", name, s + a * MS, (b - a) * MS))

    for i in range(4):
        s = i * 25 * MS
        chunk = i % 2 == 0
        w = stall_ms if i == 3 else 0.0
        ev.append(T.Ev(HOST, "python3", "engine.step", s, (24 + w) * MS))
        span("serve.tick", 0, 24 + w)
        span("serve.admit", 0, 1)
        if chunk:
            span("serve.prefill_chunk", 1, 3)
        g = 3 if chunk else 1
        span("serve.grow", g, g + 0.5)
        span("serve.decode", g + 0.5, g + 1)
        span("serve.sample", g + 1, 20 + w)
        span("serve.emit", 20 + w, 22 + w)
        if chunk:
            ev.append(T.Ev(DEV, T.MODULES, "jit_prefill_chunk_rows(3)" if named
                           else "jit__unknown(3)", s + 2 * MS, 6 * MS))
            op("fusion.1", "jit(prefill_chunk_rows)/layers/while/body/dynamic_slice", 2, 4)
            op(KERNEL, "jit(prefill_chunk_rows)/layers/while/body/attn.kernel/"
               "paged_flash_prefill/pallas_call", 4, 8)
        d = 8 if chunk else 3
        ev.append(T.Ev(DEV, T.MODULES, "jit_decode_step_rows(7)" if named
                       else "jit__unknown(7)", s + d * MS, 10 * MS))
        op(WHILE, "jit(decode_step_rows)/layers/while", d, d + 10)
        op("copy.62", f"{DEC}/dynamic_slice", d, d + 6)
        op(KERNEL, f"{DEC}/closed_call/attn.kernel/paged_flash_decode/pallas_call",
           d + 6, d + 9)
        op("copy-done.3", "", d + 9, d + 10)
        steps.append((0, 0, [1000, 3000], (0, 512, False) if chunk else None))
    return ev, ops, steps


def test_innermost_scope():
    assert S.innermost(f"{DEC}/closed_call/attn.kernel/kv_write/scatter:") == "kv_write"
    assert S.innermost(f"{DEC}/closed_call/attn.kernel/paged_flash_decode/"
                       "pallas_call:") == "attn.kernel"
    assert S.innermost(f"{DEC}/dynamic_update_slice") == "layers"
    assert S.innermost("jit(decode_step_rows)/lm_head/dot_general:") == "lm_head"
    assert S.innermost(f"{DEC}/closed_call/attn.kernel/cond/reshape;"
                       "mlp/squeeze:") == "attn.kernel"    # a fusion: its first
    assert S.innermost("") == S.innermost("jit(f)/add:") == ""
    assert S.Op(DEV, 0, 1, WHILE, "").control
    assert not S.Op(DEV, 0, 1, KERNEL, "").control


def test_tf_op_is_read_from_the_event_metadata(tmp_path):
    """ProfileData shows an event's own stats only; ``tf_op`` sits in the
    stats of its metadata, as a string or interned (``ref_value``)."""
    from jax.profiler import ProfileData

    txt = f"""
    planes {{ id: 1 name: "{DEV}"
      lines {{ id: 1 name: "{T.OPS}" timestamp_ns: 0
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 2000000
                  stats {{ metadata_id: 4 uint64_value: 7 }} }}
        events {{ metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }}
        events {{ metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "%copy.62 = bf16[2] copy(%p)"
          stats {{ metadata_id: 2 str_value: "{DEC}/dynamic_slice:" }}
          stats {{ metadata_id: 3 int64_value: 49216 }} }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "{KERNEL.replace('"', '\\"')}"
          stats {{ metadata_id: 2 ref_value: 5 }} }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.1 = f32[2] fusion(%p)" }} }}
      stat_metadata {{ key: 2 value {{ id: 2 name: "tf_op" }} }}
      stat_metadata {{ key: 3 value {{ id: 3 name: "bytes_accessed" }} }}
      stat_metadata {{ key: 4 value {{ id: 4 name: "device_offset_ps" }} }}
      stat_metadata {{ key: 5 value {{ id: 5 name: "{DEC}/attn.kernel/paged_flash_decode/pallas_call:" }} }}
    }}
    planes {{ id: 2 name: "{HOST}" }}"""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    meta = S.metadata_stats(str(path))
    assert meta[(DEV, "%copy.62 = bf16[2] copy(%p)")] == {
        "tf_op": f"{DEC}/dynamic_slice:", "bytes_accessed": 49216}
    ops = S.load_ops(str(path))
    assert [(o.scope, o.dur) for o in ops] == [("layers", 2000.0),
                                               ("attn.kernel", 1000.0), ("", 1000.0)]


def _xspace(path, starts_ms):
    """A trace with a ``bench.trace`` span over 0-10 ms and one scoped
    ``copy.62`` op starting at each of ``starts_ms``, each 1 ms long."""
    from jax.profiler import ProfileData

    ops = "".join(f"events {{ metadata_id: 1 offset_ps: {int(a * 1e9)} "
                  "duration_ps: 1000000000 }" for a in starts_ms)
    txt = f"""
    planes {{ id: 1 name: "{DEV}"
      lines {{ id: 1 name: "{T.OPS}" timestamp_ns: 0 {ops} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "copy.62"
          stats {{ metadata_id: 2 str_value: "{DEC}/dynamic_slice:" }} }} }}
      stat_metadata {{ key: 2 value {{ id: 2 name: "tf_op" }} }}
    }}
    planes {{ id: 2 name: "{HOST}"
      lines {{ id: 1 name: "python3" timestamp_ns: 0
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "bench.trace" }} }}
    }}"""
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))


def test_ops_come_from_the_trace_the_view_read(tmp_path):
    """Another trace written later under the same root (another cell's, a
    tool's) is not split against this View's calls: the ops are taken from
    the newest trace whose device ops in the window are the View's."""
    import os

    own, later = tmp_path / "cell" / "own.xplane.pb", tmp_path / "other" / "t.xplane.pb"
    _xspace(own, [1, 4])
    _xspace(later, [2, 5, 7])
    os.utime(own, (1, 1))
    os.utime(later, (2, 2))
    v = T.View.from_dir(own.parent, [0, 0, 0, 0], [], DIMS, PEAK)
    ops = S.trace_ops(v, str(tmp_path))
    assert [(o.start, o.scope) for o in ops] == [(1 * MS, "layers"), (4 * MS, "layers")]
    newer = T.View.from_dir(later.parent, [0, 0, 0, 0], [], DIMS, PEAK)
    assert [o.start for o in S.trace_ops(newer, str(tmp_path))] == [2 * MS, 5 * MS, 7 * MS]
    assert S.trace_ops(v, str(later.parent)) == []


def test_readers_on_a_named_trace():
    ev, ops, steps = _trace()
    v = T.View(ev, steps, DIMS, PEAK)
    dec = S.scope_ms(v, "decode_step_rows", ops)
    assert dec == {"layers": pytest.approx(6.0), "attn.kernel": pytest.approx(3.0),
                   "": pytest.approx(1.0)}
    assert sum(dec.values()) == pytest.approx(v.call_ms("decode_step_rows"))
    assert S.scope_ms(v, "prefill_chunk_rows", ops) == {
        "layers": pytest.approx(2.0), "attn.kernel": pytest.approx(4.0)}
    assert S.arena_move_ms(v, ops) == pytest.approx(6.0)
    assert v.call_ms("prefill_chunk_rows") == pytest.approx(6.0)
    split = S.idle_ms(v)
    assert split == {"serve.admit": pytest.approx(1.0),
                     "serve.prefill_chunk": pytest.approx(0.5),
                     "serve.grow": pytest.approx(0.25),
                     "serve.decode": pytest.approx(0.25),
                     "serve.sample": pytest.approx(5.0),
                     "serve.emit": pytest.approx(2.0),
                     "serve.tick": pytest.approx(2.0)}
    assert S.host_stall_ms(v) == pytest.approx(11.0)


def test_one_long_stall_moves_a_mean_not_the_median():
    """Ticks idle the device 8, 14, 8 and 14 ms. One readback wait of 2 s
    with the device idle lifts the mean idle per tick about 46-fold; the
    median, which ``host_stall_ms`` reads, does not move."""
    calm = T.View(*_trace()[::2], DIMS, PEAK)
    stalled = T.View(*_trace(stall_ms=2000.0)[::2], DIMS, PEAK)
    assert [sum(t.values()) for t in S.tick_idle_ms(calm)] == pytest.approx(
        [8.0, 14.0, 8.0, 14.0])
    assert sum(S.idle_ms(calm).values()) == pytest.approx(11.0)
    assert sum(S.idle_ms(stalled).values()) == pytest.approx(511.0)
    assert S.idle_ms(stalled)["serve.sample"] == pytest.approx(505.0)
    assert S.host_stall_ms(calm) == S.host_stall_ms(stalled) == pytest.approx(11.0)


def test_readers_on_a_program_without_names():
    """The parent of the change that added the names: the readers find
    nothing and say so, and the chunk time still comes from the View."""
    ev, ops, steps = _trace(named=False)
    v = T.View(ev, steps, DIMS, PEAK)
    assert S.arena_move_ms(v, ops) is None
    assert S.idle_ms(v) is None and S.host_stall_ms(v) is None
    assert v.call_ms("prefill_chunk_rows") == pytest.approx(6.0)
    assert S.scope_ms(v, "verify_chunk_rows", ops) == {}
    assert S.trace_ops(v, str(bench.ROOT / "no-such-trace-dir")) == []


def test_readers_on_a_cpu_trace(tmp_path):
    """A real trace written on the CPU: its ops run on host threads, not on
    a device plane, so the readers find nothing to read and say so."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.trace"):
            with jax.profiler.TraceAnnotation("serve.tick"):
                f(x).block_until_ready()
    v = T.View.from_dir(tmp_path, [0, 0, 0, 0], [], DIMS, PEAK)
    assert S.trace_ops(v, str(tmp_path)) == []
    assert v.devices == [] and len(S._ticks(v)) == 1
    assert S.arena_move_ms(v, []) is None and S.host_stall_ms(v) is None
