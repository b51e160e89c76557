"""Open-loop arrivals at a fixed rate: requests are due on a schedule,
whether or not earlier ones have finished (independent users).

Mix parameters: ``rate_rps``, ``prompt_len`` [lo, hi], ``output_len``
[lo, hi].  Every seed gets the same work in another order: the
inter-arrival gaps are the n quantiles of an exponential distribution
(n = rate × window), scaled to fill the window exactly, and the lengths
are n evenly spaced draws from their uniform ranges; the seed permutes
both and draws the token ids.  So two seeds differ in order and content,
never in how much there is to do.
"""

from __future__ import annotations

import numpy as np


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """n evenly spaced integer draws from U{lo..hi}."""
    return lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1)
                         ).astype(np.int64)


class Source:
    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        rng = np.random.default_rng(seed)
        n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
        gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
        gaps *= seconds / gaps.sum()
        self._due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        self._plen = rng.permutation(spread(*mix["prompt_len"], n))
        self._olen = rng.permutation(spread(*mix["output_len"], n))
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
