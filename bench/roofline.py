"""Peaks of the chip and the work of one sampled walk step.

The advance is bound by memory traffic, not arithmetic: a node2vec step does
a handful of integer compares and no floating-point work worth counting, so
its roofline is the bytes it must move over the chip's HBM bandwidth.
:func:`advance_bytes_per_step` counts the walk's work, not the code's: the
walk state read and written (``prev``, ``cur``, ``hop``: 3 x int32 each
way), the two CSR offsets of ``cur`` (2 x int32), one neighbour id (int32)
and, when the corpus is recorded, one trace write (int32).  Rejected
proposals, binary-search probes and padding are the code's choice and are
not counted, so a rewritten kernel is measured against the same work.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

INT32 = 4


def advance_bytes_per_step(*, record: bool) -> int:
    state = 2 * 3 * INT32
    offsets = 2 * INT32
    neighbour = INT32
    trace = INT32 if record else 0
    return state + offsets + neighbour + trace


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def advance_roofline_pct(
    steps: int, advance_s: float, *, record: bool, hbm_bytes_per_s: float, chips: int = 1
):
    """Share (%) of the bytes-bound roofline of ``chips`` chips, each with
    ``hbm_bytes_per_s``: the walk's bytes over the bandwidth of every chip the
    program ran on, against its device time (``advance_s``, per chip, as the
    trace reduction averages it); ``None`` without device time."""
    if advance_s <= 0 or steps <= 0:
        return None
    bytes_per_s = chips * hbm_bytes_per_s
    return 100.0 * steps * advance_bytes_per_step(record=record) / bytes_per_s / advance_s
