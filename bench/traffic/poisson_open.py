"""Open-loop arrivals at a fixed rate: requests are due on a schedule,
whether or not earlier ones have finished (independent users).

Mix parameters: ``rate_rps``, ``prompt_len`` [lo, hi], ``output_len``
[lo, hi].  The inter-arrival gaps are the n quantiles of an exponential
distribution (n = rate × window), scaled to fill the window exactly, and
the lengths are n evenly spaced draws from their uniform ranges.

Every seed gets the same work: the order of gaps and lengths is drawn
once, from a fixed seed, and the seed draws only the token ids.  The
order of the gaps is the schedule: it decides where arrivals bunch, and
so the queue behind them.  Gaps permuted by the seed gave each seed
bursts of its own, and tails that moved with the seed.
"""

from __future__ import annotations

import numpy as np

# the one order of gaps and lengths, the same for every --seed
_ORDER_SEED = 0


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """n evenly spaced integer draws from U{lo..hi}."""
    return lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1)
                         ).astype(np.int64)


class Source:
    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        order = np.random.default_rng(_ORDER_SEED)
        n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
        gaps = order.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
        gaps *= seconds / gaps.sum()
        self._due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        self._plen = order.permutation(spread(*mix["prompt_len"], n))
        self._olen = order.permutation(spread(*mix["output_len"], n))
        self._seed = seed
        self._vocab = vocab
        self._next = 0

    def _request(self, i: int):
        rng = np.random.default_rng([self._seed, i])
        toks = rng.integers(0, self._vocab, int(self._plen[i]), dtype=np.int32)
        return toks, int(self._olen[i]), float(self._due[i])

    def poll(self, now: float) -> list:
        """Requests due by ``now`` (seconds into the window), not yet
        handed out: (tokens, max_new_tokens, due)."""
        out = []
        while self._next < len(self._due) and self._due[self._next] <= now:
            out.append(self._request(self._next))
            self._next += 1
        return out

    def finished(self, due: float, now: float) -> None:
        """Completions do not change an open loop's schedule."""

    def next_due(self):
        return float(self._due[self._next]) if self._next < len(self._due) \
            else None
