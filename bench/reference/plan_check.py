"""Plain check of a served plan against the traffic it was asked for.

The device exchange only needs the plan's inter-server permutations to
move each ordered server pair's bytes, each stage incast-free.  So: the
server matrix is the GPU matrix summed over each server's GPU blocks
with the diagonal dropped; every permutation stage sends from each live
server to one other server, no two to the same receiver, never more
than its slot; and the bytes a stage list delivers per pair equal the
server matrix.  The number returned is the largest per-pair mismatch as
a share of the largest pair's bytes (0 for an exact plan).
"""

from __future__ import annotations

import numpy as np


def server_matrix(gpu_matrix: np.ndarray, m_gpus: int) -> np.ndarray:
    n = gpu_matrix.shape[0] // m_gpus
    t = gpu_matrix.reshape(n, m_gpus, n, m_gpus).sum(axis=(1, 3))
    np.fill_diagonal(t, 0.0)
    return t


def _stages(plan):
    """(perm [n], sent [n], slot bytes [n]) of every permutation stage."""
    for phase in plan.phases:
        kind = getattr(phase, "kind", "")
        if kind == "permutation":
            perm = np.asarray(phase.perm, dtype=np.int64)
            slots = (np.full(len(perm), float(phase.size))
                     if phase.slots is None else np.asarray(phase.slots))
            yield perm, np.asarray(phase.sent, dtype=np.float64), slots
        elif kind == "permutation_block":
            perms = np.asarray(phase.perms, dtype=np.int64)
            sent = np.asarray(phase.sent, dtype=np.float64)
            if phase.slots is None:
                slots = np.broadcast_to(
                    np.asarray(phase.sizes, dtype=np.float64)[:, None],
                    perms.shape)
            else:
                slots = np.asarray(phase.slots, dtype=np.float64)
            for k in range(perms.shape[0]):
                yield perms[k], sent[k], slots[k]


def plan_error(plan, gpu_matrix: np.ndarray, m_gpus: int) -> float:
    """Largest per-pair byte mismatch over the largest pair's bytes;
    ``inf`` for a structurally invalid stage."""
    target = server_matrix(np.asarray(gpu_matrix, dtype=np.float64), m_gpus)
    n = target.shape[0]
    moved = np.zeros_like(target)
    for perm, sent, slots in _stages(plan):
        live = perm >= 0
        dst = perm[live]
        src = np.nonzero(live)[0]
        if (dst >= n).any() or (dst == src).any() \
                or len(np.unique(dst)) != len(dst) \
                or (sent[live] < 0).any() \
                or (sent[live] > slots[live] * (1 + 1e-9) + 1e-6).any() \
                or (sent[~live] != 0).any():
            return float("inf")
        np.add.at(moved, (src, dst), sent[live])
    scale = max(float(target.max()), 1.0)
    return float(np.abs(moved - target).max() / scale)
