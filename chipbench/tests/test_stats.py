"""Percentile and rate arithmetic of the window, and that a stall moves
both the tail and the rate."""

import numpy as np
import pytest

import stats


def test_percentile_matches_numpy():
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for q in (0, 25, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _steady(n_req=20, n_tok=50, itl=0.02, start=0.0):
    out = []
    for i in range(n_req):
        due = start + 0.5 * i
        out.append(stats.Track(due=due, times=[due + 0.1 + itl * k for k in range(n_tok)]))
    return out


def test_rate_ttft_itl_hand_counts():
    tr = [stats.Track(due=0.0, times=[0.25, 0.5, 0.75]),
          stats.Track(due=0.5, times=[1.0, 1.5]),
          stats.Track(due=2.5, times=[]),           # due in window, never served
          stats.Track(due=-1.0, times=[0.1, 0.3])]  # due before the window
    m = stats.window_metrics(tr, 0.0, 2.0)
    assert m["output_tok_s"] == pytest.approx(7 / 2.0)
    assert m["attempted"] == 2 and m["failed"] == 0   # 2.5 is past the close
    assert m["ttft_p50_ms"] == pytest.approx(1e3 * 0.375)
    itl = [0.25, 0.25, 0.5, 0.2]
    assert m["itl_p95_ms"] == pytest.approx(1e3 * np.percentile(itl, 95))
    m2 = stats.window_metrics(tr, 0.0, 3.0)
    assert m2["attempted"] == 3 and m2["failed"] == 1


def test_stall_moves_tail_and_rate():
    base = _steady()
    t1 = 10.0
    m0 = stats.window_metrics(base, 0.0, t1)
    # the same tokens, but every token after t=4 s comes 2 s later
    stalled = [stats.Track(due=t.due, times=[x if x < 4.0 else x + 2.0 for x in t.times])
               for t in base]
    m1 = stats.window_metrics(stalled, 0.0, t1)
    assert m1["output_tok_s"] < m0["output_tok_s"]
    assert m1["ttft_p95_ms"] > m0["ttft_p95_ms"]
    assert m1["itl_p95_ms"] >= m0["itl_p95_ms"]
    assert m1["ttft_p50_ms"] >= m0["ttft_p50_ms"]
