"""Prompt token ids: a bounded Zipf law over the vocabulary.

Natural text has Zipf-like token frequencies, so the benchmark draws
prompt ids with P(rank r) proportional to 1 / r**s over the whole
vocabulary (id = rank - 1).  Repeated ids route to the same experts in the
first MoE layer, which is what makes the capacity drops of the served path
depend on the traffic.
"""

from __future__ import annotations

import numpy as np


class ZipfPrompts:
    """Batches of prompts drawn from one seeded generator."""

    def __init__(self, vocab: int, s: float, seed_seq):
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -float(s))
        self._cdf = cdf / cdf[-1]
        self._rng = np.random.default_rng(seed_seq)

    def batch(self, batch: int, length: int) -> np.ndarray:
        u = self._rng.random((batch, length))
        ids = np.searchsorted(self._cdf, u, side="right")
        return np.minimum(ids, len(self._cdf) - 1).astype(np.int32)
