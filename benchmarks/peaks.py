"""The table of peaks, keyed by ``device_kind``.  An unknown device is an
error, not a default."""

from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"benchmarks/peaks.json knows {sorted(table)}")
    return table[device_kind]
