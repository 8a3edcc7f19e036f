"""Device: share (%) of the traced window in which no operation ran on the chip."""


def read(r):
    if r.trace is None or not r.trace_s:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace_s)
