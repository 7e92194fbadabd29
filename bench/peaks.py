"""Published per-chip peaks, keyed by ``device_kind`` (``peaks.json``).

A device that is not in the table is an error, never a default: a share of
a peak is only as good as the peak it divides by.
"""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    """The table has no peaks for this ``device_kind``."""


def peaks(device_kind: str, path: str = PATH) -> dict:
    with open(path) as f:
        table = json.load(f)
    try:
        return dict(table["devices"][device_kind], source=table["source"])
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in {path}; known: "
            f"{sorted(table['devices'])}") from None
