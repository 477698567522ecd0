"""Counts from shapes that more than one architecture or kernel shares:
the causal (query, key) pairs of attention, and the bytes the a2a kernels
must move.  A model's useful FLOPs are its architecture module's
(``bench/arch/<arch>.py``: ``prefill_flops``, ``decode_step_flops``)."""

from __future__ import annotations

from typing import Optional


def keys_seen(first_pos: int, n_queries: int,
              window: Optional[int]) -> int:
    """Sum over queries at positions first_pos .. first_pos+n-1 of the
    number of keys each attends to (causal, optionally banded)."""
    total = 0
    for p in range(first_pos, first_pos + n_queries):
        total += p + 1 if window is None else min(p + 1, window)
    return total


def a2a_kernel_bytes(in_rows: int, out_rows: int, width: int,
                     itemsize: int) -> int:
    """HBM bytes one ``a2a_pack`` / ``a2a_unpack`` call must move.

    Pack gathers one block per output slot (every output row is read from
    the input once and written once); unpack scatters every input slot to
    one output block (every input row read once, written once; output
    blocks no slot names are never touched).  Either way the rows moved
    are the smaller of the two row counts, each read and written once.
    """
    return 2 * min(in_rows, out_rows) * width * itemsize
