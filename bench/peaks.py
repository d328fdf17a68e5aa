"""The table of peaks (`peaks.json`), keyed by JAX's `device_kind`."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str, key: str, path: str = PATH) -> float:
    """One peak of a device; a device missing from the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return float(table[device_kind][key])
