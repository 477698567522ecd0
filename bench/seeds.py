"""Every random input of a run derives from ``--seed`` and a tag naming
what it is for, so two runs of one seed get the same inputs, and seeds of
any size (beyond 32 bits) work."""

from __future__ import annotations

import zlib

import numpy as np


def seed_seq(seed: int, tag: str, *more: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed), zlib.crc32(tag.encode()),
                                   *more])
