"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).
A kind that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]
