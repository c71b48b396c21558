"""The table of device peaks, keyed by ``device_kind``. A device that is
not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; "
            f"known: {sorted(table)} (add a row to harness/peaks.json "
            f"with its source)")
    return table[device_kind]
