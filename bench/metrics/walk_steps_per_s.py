"""Sampled walk steps completed between the window's two advance-call
boundaries, over that time (host clock)."""


def read(r):
    if r.kind != "batch" or r.window_s <= 0:
        return None
    return r.counters["steps_sampled"] / r.window_s
