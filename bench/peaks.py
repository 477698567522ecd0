"""Chip peaks by ``device_kind``, from ``peaks.json`` beside this file.

A device that is not in the table is an error, never a default: a share of
a peak is only meaningful against that chip's own published numbers.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    try:
        return dict(table[device_kind])
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path}; known: "
            f"{sorted(table)}") from None
