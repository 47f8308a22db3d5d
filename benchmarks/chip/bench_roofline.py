"""Peak table and the bytes any Tucker decomposition must move.

A Tucker decomposition of a dense tensor reads the whole input at least
once and writes its core and factors once, whatever computes it.  That
byte count over the chip's HBM bandwidth is a floor on the time of every
implementation, so a share of it cannot pass 100% unless the time leaves
out part of the work.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE.name}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


def tucker_floor_bytes(shape, ranks, itemsize: int = 4) -> int:
    """One read of the input plus one write of the core and the factors."""
    core = math.prod(ranks)
    factors = sum(i * r for i, r in zip(shape, ranks))
    return (math.prod(shape) + core + factors) * itemsize


def tucker_floor_s(shape, ranks, hbm_bytes_per_s: float,
                   itemsize: int = 4) -> float:
    return tucker_floor_bytes(shape, ranks, itemsize) / hbm_bytes_per_s
