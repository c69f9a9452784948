"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and turns them, with a seed, into request sizes, prompts and arrival gaps.

Sizes and gaps are drawn by stratified quantiles in blocks of ``block``
requests: every block holds the same multiset of sizes (and of gaps), and the
seed only permutes each block and picks the prompt tokens. So every seed
offers the same work in another order, and runs with different seeds differ
no more than runs of one seed. The quantile arithmetic (lognormal and uniform
sizes, exponential Poisson gaps) follows the seeded generators of the
program's trace module, with times in seconds instead of decode ticks.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    prompt: np.ndarray      # int32 token ids
    max_tokens: int


def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a size distribution at probabilities ``u``, rounded
    and clipped to [min, max]."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _strata(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def block_sizes(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """The multiset of (prompt, output) sizes each block holds, paired
    through independent strata so prompt and output lengths are not tied."""
    u = _strata(mix["block"])
    prompts = _quantile(mix["prompt"], u)
    # pair output strata in a fixed scrambled order (same for every seed)
    order = np.random.default_rng(0).permutation(mix["block"])
    outputs = _quantile(mix["output"], u[order])
    return prompts, outputs


def block_gaps(mix: dict) -> np.ndarray:
    """Exponential inter-arrival gaps (seconds) at ``rate_per_s``, one
    stratum each, so every block spans the same time."""
    u = _strata(mix["block"])
    return -np.log1p(-u) / mix["rate_per_s"]


class Traffic:
    """Deterministic request stream for one seed: ``next_request()`` yields
    requests in order; ``gaps(n)`` the first n arrival gaps (open loop)."""

    def __init__(self, mix: dict, seed: int, vocab: int, max_len: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.prompts, self.outputs = block_sizes(mix)
        if int(self.prompts.max() + self.outputs.max()) > max_len:
            raise ValueError(
                f"traffic needs {int(self.prompts.max() + self.outputs.max())}"
                f" positions, the cell's slots hold {max_len}")
        self._order: list[int] = []
        self._gap_order: list[int] = []
        self._n = 0

    def next_request(self) -> Req:
        if not self._order:
            self._order = list(self.rng.permutation(self.mix["block"]))
        j = self._order.pop()
        n_prompt = int(self.prompts[j])
        prompt = self.rng.integers(0, self.vocab, n_prompt, dtype=np.int32)
        req = Req(self._n, prompt, int(self.outputs[j]))
        self._n += 1
        return req

    def next_gap(self, rate_per_s: float | None = None) -> float:
        """The next open-loop gap; ``rate_per_s`` overrides the mix's rate
        (the capacity sweep) by scaling the same strata."""
        if not self._gap_order:
            self._gap_order = list(self.rng.permutation(self.mix["block"]))
        g = float(block_gaps(self.mix)[self._gap_order.pop()])
        if rate_per_s is not None:
            g *= self.mix["rate_per_s"] / rate_per_s
        return g


def first_residual(out_len: int, k: int, n: int) -> int:
    """Closed-loop start: client k of n begins mid-request, with the
    remaining share (k + 0.5)/n of its output left, so completions are
    spread over the window instead of arriving together."""
    return max(1, int(math.ceil(out_len * (k + 0.5) / n)))
