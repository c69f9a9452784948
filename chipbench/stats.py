"""Window arithmetic: rates, percentiles, TTFT and inter-token gaps from the
host-clock token events of one measured window."""
from __future__ import annotations

import dataclasses
import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default method), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Track:
    """One request as the client saw it: when it was due, when it was
    handed to the engine, and the host-clock time of each token."""
    due: float
    added: float = math.nan
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finished_at: float = math.nan

    @property
    def first(self) -> float:
        return self.times[0] if self.times else math.nan


def window_metrics(tracks, t0: float, t1: float) -> dict:
    """End-to-end numbers of the window [t0, t1):

    - ``output_tok_s``: every token emitted in the window over its length;
    - ``ttft_*``: from each request DUE in the window to its first token,
      wherever that token fell (the caller follows them past the close);
      a request with no first token is counted in ``failed``, not here;
    - ``itl_p95_ms``: every gap between consecutive tokens of a request
      that both fell in the window."""
    n_tok = 0
    ttft, itl = [], []
    attempted = failed = 0
    for tr in tracks:
        in_win = [t for t in tr.times if t0 <= t < t1]
        n_tok += len(in_win)
        itl.extend(b - a for a, b in zip(in_win, in_win[1:]))
        if t0 <= tr.due < t1:
            attempted += 1
            if tr.times:
                ttft.append(tr.first - tr.due)
            else:
                failed += 1
    out = {"attempted": attempted, "failed": failed,
           "output_tok_s": n_tok / (t1 - t0), "n_ttft": len(ttft),
           "n_itl": len(itl)}
    if ttft:
        out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    if itl:
        out["itl_p95_ms"] = 1e3 * percentile(itl, 95)
    return out
