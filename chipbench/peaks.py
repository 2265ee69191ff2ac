"""The chip's published peaks, keyed by the ``device_kind`` JAX reports."""
from __future__ import annotations

import json
import os


class UnknownDevice(KeyError):
    pass


def peaks_for(kind: str) -> dict:
    """Peaks of ``kind``; a kind the table does not hold is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r}; the table "
                            f"holds {sorted(table)}")
    return table[kind]
