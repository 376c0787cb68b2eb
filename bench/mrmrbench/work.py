"""The work a fit requires of the count layer, from the configuration and
the fit's pass count alone, and the chip's peaks.

A scoring pass must read every observation of every feature once (one
byte: the int8 code), the pass target's code (one byte per row) and the
row's validity (one byte per row).  How a kernel reads them, widens them
or stages them does not change this number, so a rewritten, fused or
replaced count kernel is measured against the same work.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def count_bytes_per_pass(rows: int, features: int) -> int:
    """HBM bytes one scoring pass has to read."""
    return int(rows) * (int(features) + 2)


def count_bytes_per_fit(rows: int, features: int, passes: int) -> int:
    return int(passes) * count_bytes_per_pass(rows, features)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind not in the table is an error."""
    entries = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in entries:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(entries)}"
        )
    return entries[device_kind]
