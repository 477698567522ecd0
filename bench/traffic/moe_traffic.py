"""Plan-request traffic: MoE dispatch matrices and their drift.

Copies of two generators from the repository, kept here so that the
benchmark's traffic cannot move with the program:

* ``moe_matrix`` is ``repro.core.traffic.moe_workload``'s matrix: top-k
  gating over Dirichlet expert popularity, folded onto host GPUs, self
  traffic dropped.  Same generator stream, so the same seed gives the same
  matrix.
* ``DriftStream`` is ``benchmarks/fig_dynamic.py:_drift_trajectory`` as a
  stream: each step either replays an earlier signature or perturbs a
  share of the entries, consuming the generator in the same order.
"""

from __future__ import annotations

from typing import List

import numpy as np


def moe_matrix(n_gpus: int, tokens_per_gpu: int, bytes_per_token: int,
               top_k: int = 2, expert_skew: float = 0.6, seed=0,
               n_experts: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    e = n_experts or n_gpus
    popularity = rng.dirichlet(np.full(e, expert_skew))
    counts = rng.multinomial(
        tokens_per_gpu, popularity, size=(n_gpus, top_k)).sum(axis=1)
    w = np.zeros((n_gpus, n_gpus))
    np.add.at(w.T, np.arange(e) % n_gpus, counts.astype(np.float64).T)
    np.fill_diagonal(w, 0.0)
    return w * float(bytes_per_token)


class DriftStream:
    """One plan family's drifting dispatch matrices, step by step."""

    def __init__(self, n_gpus: int, tokens_per_gpu: int,
                 bytes_per_token: int, *, top_k: int = 2,
                 expert_skew: float = 0.6, repeat_p: float = 0.3,
                 drift_p: float = 0.02, drift_low: float = 0.8,
                 drift_high: float = 1.2, seed=0):
        self._rng = np.random.default_rng(seed)
        self._repeat_p = repeat_p
        self._drift_p = drift_p
        self._low, self._high = drift_low, drift_high
        self._mats: List[np.ndarray] = [moe_matrix(
            n_gpus, tokens_per_gpu, bytes_per_token, top_k=top_k,
            expert_skew=expert_skew, seed=seed)]
        self._first = True

    def next(self) -> np.ndarray:
        """The next matrix."""
        if self._first:
            self._first = False
            return self._mats[0]
        rng, mats = self._rng, self._mats
        if rng.random() < self._repeat_p and len(mats) > 1:
            mats.append(mats[int(rng.integers(len(mats)))])
            return mats[-1]
        nxt = mats[-1].copy()
        drift = rng.random(nxt.shape) < self._drift_p
        nxt[drift] *= rng.uniform(self._low, self._high,
                                  size=int(drift.sum()))
        np.fill_diagonal(nxt, 0.0)
        mats.append(nxt)
        return nxt
