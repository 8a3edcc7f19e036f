"""Device advance: share (%) of the bytes-bound roofline: the sampled steps of
the traced span times the bytes a step needs (``roofline.py``) over the
chip's HBM bandwidth, against the device time of the ``pair_advance`` module
in that span."""

from roofline import advance_roofline_pct


def read(r):
    if r.trace is None or not r.peaks:
        return None
    return advance_roofline_pct(
        r.trace_counters["steps_sampled"],
        r.trace["advance_s"],
        record=r.record,
        hbm_bytes_per_s=r.peaks["hbm_bytes_per_s"],
    )
