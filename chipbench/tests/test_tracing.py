"""The trace reducer: interval arithmetic, and every reading of a View on a
hand-made trace whose answers are known."""
import pytest

import flops as F
import tracing as T

PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
DEV, HOST = "/device:TPU:0", "/host:CPU"
KERNEL = ("%branch_0_fun.9 = bf16[16,16,1,64] custom-call(s32[16,256] %copy-done), "
          "custom_call_target=\"tpu_custom_call\"")


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (40, 45)]
    assert T.union_length(iv, 0, 50) == 30
    assert T.union_length(iv, 8, 22) == 9
    assert T.gaps(iv, 0, 50) == [(15, 20), (30, 40), (45, 50)]
    assert T.gaps([], 0, 5) == [(0, 5)]


def _synthetic():
    ms = 1e6
    ev = [T.Ev(HOST, "python3", "bench.trace", 0, 100 * ms)]
    steps = []
    for i in range(4):                         # four ticks of 25 ms
        s = i * 25 * ms
        ev.append(T.Ev(HOST, "python3", "engine.step", s, 25 * ms))
        ev.append(T.Ev(DEV, T.MODULES, "jit__unknown(7)", s + 5 * ms, 10 * ms))
        ev.append(T.Ev(DEV, T.OPS, "fusion.3", s + 5 * ms, 6 * ms))
        ev.append(T.Ev(DEV, T.OPS, KERNEL, s + 11 * ms, 4 * ms))
        steps.append((0, 0, [1000, 3000], None))
    return ev, steps


def test_view_readings():
    ev, steps = _synthetic()
    dims = F.Dims(d=1024, heads=16, kv_heads=16, head_dim=64, ff=2816,
                  vocab=151936, layers=24, gated_mlp=True)
    v = T.View(ev, steps, dims, PEAK)
    assert v.window_s == pytest.approx(0.1)
    assert v.busy_s == pytest.approx(0.04)
    assert v.idle_share() == pytest.approx(60.0)
    assert v.call_ms("decode_step_rows") == pytest.approx(10.0)
    assert v.call_ms("prefill_chunk_rows") is None
    assert v.tick_host_ms() == pytest.approx(15.0)
    need = 4 * F.min_time(*F.decode_attn(dims, [1000, 3000]), 197e12, 819e9)[0]
    assert v.decode_roofline() == pytest.approx(100 * need / 0.016)
    assert v.prefill_roofline() is None
    fl = 4 * (F.model_flops(dims, 2, 2) + F.decode_attn(dims, [1000, 3000])[0])
    assert v.mfu() == pytest.approx(100 * fl / (0.1 * 197e12))
    b = v.breakdown()
    assert b["device_ops"][0] == ["fusion.3", pytest.approx(0.024)]
    assert b["device_ops"][1] == ["branch_0_fun.9 tpu_custom_call", pytest.approx(0.016)]
    assert b["idle_gaps"][0][0] == "engine.step"
    assert len(b["idle_gaps"]) <= 10


def test_recorded_chip_trace():
    """Two engine ticks of the qwen05b-longdoc cell recorded on a TPU v5e
    (op names cut to what the reducer reads). Both ticks ran one decode
    step and no chunk; the trace does not carry the rows' lengths, so only
    what the trace alone decides is checked."""
    import os

    import bench

    ev = T.load_events(os.path.join(os.path.dirname(__file__), "data",
                                    "longdoc_trace.json.gz"))
    steps = [[0.0, 0.0, [1] * 16, None]] * 2
    conf = bench.load_json(bench.HERE / "configs" / "qwen1.5-0.5b.json")
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    v = T.View(ev, steps, F.dims_of(conf), peak)
    assert v.devices == ["/device:TPU:0"]
    assert 0 < v.busy_s <= v.window_s
    assert v.idle_share() == pytest.approx(2.2, abs=0.5)
    dec = v.calls("decode_step_rows")
    assert len(dec) == 2 and v.calls("prefill_chunk_rows") == []
    assert v.call_ms("decode_step_rows") == pytest.approx(169.84, abs=0.01)
    assert len(v.calls("greedy_token_rows")) == 2
    kern = v.kernel_events("decode_step_rows")
    assert len(kern) == 2 * 24                  # one paged flash call per layer
    assert 0 < sum(e.dur for e in kern) < sum(e.dur for e in dec)
    assert v.prefill_roofline() is None
    assert 0 < v.tick_host_ms() < 10
    b = v.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert "branch_0_fun.9 tpu_custom_call" in [n for n, _ in b["device_ops"]]
