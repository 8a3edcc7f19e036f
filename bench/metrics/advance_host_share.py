"""Host-device call: share (%) of the window inside the program's advance
calls (``IOStats.exec_time``: dispatch, device, ``block_until_ready`` and the
copy back; not the packing and upload before it)."""


def read(r):
    if r.window_s <= 0:
        return None
    return 100.0 * r.counters["exec_time"] / r.window_s
