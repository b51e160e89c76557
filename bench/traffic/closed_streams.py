"""Closed-loop streams: each of ``streams`` clients sends its next request
``think_s`` after its previous one completed (callers that each wait for
their reply, such as agents replanning a long task).

Mix parameters: ``streams``, ``prompt_len`` [lo, hi], ``output_len``
[lo, hi], ``think_s``.  Every stream sends its first request as the
window opens.  (Staggered starts would not spread the prefills: the
engine runs a chunk of up to 64 decode steps for the first request
before it sees the next.)  Requests are taken in order from one pool whose
lengths are evenly spaced draws from the uniform ranges.

Every seed gets the same work: the pool's order is drawn once, from a
fixed seed, and the seed draws only the token ids.  In a closed loop the
order of the lengths is the schedule: it decides which replies end on the
same decode step, and so how many prompts queue for prefill together.  A
pool permuted by the seed gave each seed a schedule of its own and moved
the first-token tail by whole prefills from seed to seed.
"""

from __future__ import annotations

import heapq

import numpy as np

_POOL = 4096
# the pool's one order, the same for every --seed
_ORDER_SEED = 0


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """n evenly spaced integer draws from U{lo..hi}."""
    return lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1)
                         ).astype(np.int64)


class Source:
    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        order = np.random.default_rng(_ORDER_SEED)
        self._plen = order.permutation(spread(*mix["prompt_len"], _POOL))
        self._olen = order.permutation(spread(*mix["output_len"], _POOL))
        self._think = float(mix["think_s"])
        self._ready = [0.0] * int(mix["streams"])
        self._seed = seed
        self._vocab = vocab
        self._next = 0

    def poll(self, now: float) -> list:
        """Requests of the streams that are ready by ``now``:
        (tokens, max_new_tokens, due)."""
        out = []
        while self._ready and self._ready[0] <= now:
            due = heapq.heappop(self._ready)
            i = self._next % _POOL
            self._next += 1
            rng = np.random.default_rng([self._seed, self._next])
            toks = rng.integers(0, self._vocab, int(self._plen[i]),
                                dtype=np.int32)
            out.append((toks, int(self._olen[i]), due))
        return out

    def finished(self, due: float, now: float) -> None:
        """A stream's request completed at ``now``: it sends again after
        its think time."""
        heapq.heappush(self._ready, now + self._think)

    def next_due(self):
        return self._ready[0] if self._ready else None
