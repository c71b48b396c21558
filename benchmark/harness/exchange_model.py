"""The bytes an all_to_all must put on the interconnect, and the chip's
interconnect peak. Kept with the benchmark so that no PR that claims a
gain can change it."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "ici_peaks.json")


def link_bytes_per_chip(a2a_bytes: float, chips: int) -> float:
    """Bytes one chip sends over its links. The program counts an
    all_to_all as P senders x P destinations x quota rows, the diagonal
    (what a device keeps) included: (P-1)/P of it crosses a link, and a
    chip's share of the mesh's total is 1/P."""
    if chips < 1:
        raise ValueError(f"chips={chips}")
    return a2a_bytes * (chips - 1) / chips / chips


def ici_bytes_per_s(device_kind: str) -> float:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no interconnect peak recorded for device kind "
            f"{device_kind!r}; known: {sorted(table)} (add a row to "
            f"harness/ici_peaks.json with its source)")
    return float(table[device_kind]["ici_bytes_per_s"])
