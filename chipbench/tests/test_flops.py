"""FLOP and byte functions against hand counts."""
import pytest

import bench
import flops as F


def _qwen():
    return F.dims_of(bench.load_json(bench.HERE / "configs" / "qwen1.5-0.5b.json"))


def test_dims_from_published_keys():
    q = _qwen()
    assert (q.d, q.heads, q.kv_heads, q.head_dim, q.ff, q.layers, q.gated_mlp) == \
        (1024, 16, 16, 64, 2816, 24, True)


def test_matmul_params_hand_count():
    # Qwen1.5-0.5B layer: q,k,v,o 4*1024*1024, MLP 3*1024*2816
    assert _qwen().matmul_params == 24 * (4 * 1024 * 1024 + 3 * 1024 * 2816)
    two = F.Dims(d=8, heads=2, kv_heads=1, head_dim=4, ff=16, vocab=10, layers=3,
                 gated_mlp=False)
    assert two.matmul_params == 3 * (8 * 4 * (2 * 2 + 2 * 1) + 2 * 8 * 16)


def test_decode_attention_hand_count():
    d = _qwen()
    fl, by = F.decode_attn(d, [100, 300])
    # QK^T and PV: 2 * 2 * H * Dh per key, 400 keys, 24 layers
    assert fl == 24 * 4 * 16 * 64 * 400
    # K and V: 2 * 16 * 64 * 2 bytes per key; q and o: 2 * 16 * 64 * 2 per row
    assert by == 24 * (400 * 2 * 16 * 64 * 2 + 2 * 2 * 16 * 64 * 2)
    assert F.decode_attn(d, []) == (0.0, 0.0)


def test_chunk_attention_causal_triangle():
    d = _qwen()
    fl, by = F.chunk_attn(d, offset=0, valid=4)
    assert fl == 24 * 4 * 16 * 64 * (1 + 2 + 3 + 4)
    fl2, by2 = F.chunk_attn(d, offset=10, valid=2)
    assert fl2 == 24 * 4 * 16 * 64 * (11 + 12)
    assert by2 == 24 * (12 * 2 * 16 * 64 * 2 + 2 * 16 * 64 * 2 * 2)


def test_model_flops_and_bound():
    d = _qwen()
    assert F.model_flops(d, tokens=3, sampled=1) == \
        2 * d.matmul_params * 3 + 2 * 1024 * 151936
    t, which = F.min_time(197e12, 1e9, 197e12, 819e9)
    assert which == "compute" and t == pytest.approx(1.0)
    t, which = F.min_time(1.0, 819e9, 197e12, 819e9)
    assert which == "memory" and t == pytest.approx(1.0)
