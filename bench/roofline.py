"""Work counts of the kernels and the table of peaks they are held to.

A kernel's work is counted from the work its call defines, not from the
shape that implements it: a segment of ``rows`` live check-ins over ``k``
candidate slots, whatever padding the program adds.  A later kernel that
does the same job is read against the same work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; an unknown ``device_kind`` is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def masked_first_fit_bytes(rows: int, k: int) -> int:
    """HBM bytes of one masked first-fit pass: per (row, slot) an
    eligibility and a fill position read as int32; per row a position read
    and an index written as int32."""
    return 8 * rows * k + 8 * rows


def bound_s(nbytes: float, peak: dict) -> float:
    """The least time the chip could take: bytes over HBM bandwidth.  The
    kernel's compares and mins run on the vector unit, for which the table
    cites no peak; at a few int32 operations per 8 bytes the bandwidth is
    the bound."""
    return nbytes / peak["hbm_bytes_per_s"]
