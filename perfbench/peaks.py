"""The table of peaks, keyed by `device_kind`; an unknown kind is an
error and never a default."""
from __future__ import annotations

import json
import os


def device_peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"perfbench/peaks.json (known: {sorted(table)}); add "
                       f"its published peaks with their source")
    return table[device_kind]
