"""The table of peaks, keyed by the exact `device_kind` JAX reports."""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    """Peaks of one chip of `device_kind`; a kind not in the table raises."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add it with its source, never a default"
        )
    return table[device_kind]
