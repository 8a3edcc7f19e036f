"""Bi-block schedule: share (%) of the window's time slots that found no
preloaded pool drain (``pipeline_stall_slots`` / ``time_slots``)."""


def read(r):
    slots = r.counters["time_slots"]
    return None if slots <= 0 else 100.0 * r.counters["pipeline_stall_slots"] / slots
