"""The traffic generator: deterministic per seed, the same work for every
seed in another order."""
import numpy as np

import bench
import gen


def _mix(name):
    return bench.load_json(bench.HERE / "traffic" / f"{name}.json")


def _stream(mix, seed, n):
    t = gen.Traffic(mix, seed, vocab=1000, max_len=8192)
    reqs = [t.next_request() for _ in range(n)]
    gaps = [t.next_gap() for _ in range(n)] if mix["arrivals"] == "poisson" else []
    return reqs, gaps


def test_same_seed_same_stream():
    for name in ("longdoc", "chat"):
        mix = _mix(name)
        a, ga = _stream(mix, 2**31 + 77, 100)
        b, gb = _stream(mix, 2**31 + 77, 100)
        assert [r.max_tokens for r in a] == [r.max_tokens for r in b]
        assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
        assert ga == gb


def test_seeds_permute_the_same_blocks():
    mix = _mix("chat")
    n = 2 * mix["block"]
    a, ga = _stream(mix, 1, n)
    b, gb = _stream(mix, 2, n)
    sizes = lambda rs: sorted((len(r.prompt), r.max_tokens) for r in rs)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sizes(a[:mix["block"]]) == sizes(b[:mix["block"]])
    assert sizes(a) == sizes(b)
    assert np.isclose(sum(ga), sum(gb))


def test_sizes_follow_the_mix():
    mix = _mix("longdoc")
    prompts, outputs = gen.block_sizes(mix)
    assert prompts.min() >= 1024 and prompts.max() <= 3584
    assert abs(np.median(prompts) - 2500) <= 60
    assert outputs.min() >= 256 and outputs.max() <= 512


def test_rate_override_scales_gaps():
    mix = _mix("chat")
    t1 = gen.Traffic(mix, 5, 1000, 8192)
    t2 = gen.Traffic(mix, 5, 1000, 8192)
    g1 = [t1.next_gap() for _ in range(10)]
    g2 = [t2.next_gap(2 * mix["rate_per_s"]) for _ in range(10)]
    assert np.allclose(np.array(g1) / 2, g2)
    # a whole block spans n/rate on average
    g = gen.block_gaps(mix)
    assert abs(g.mean() * mix["rate_per_s"] - 1.0) < 0.05


def test_closed_loop_residuals_spread():
    res = [gen.first_residual(400, k, 16) for k in range(16)]
    assert res == sorted(res) and res[0] >= 1 and res[-1] <= 400
