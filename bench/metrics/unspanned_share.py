"""Host: share (%) of the window in which the engine's thread is inside no
program span at all: host work no layer accounts for."""


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None or r.window_s <= 0:
        return None
    busy = spans.busy(win.t_open, win.t_close, spans.thread_of("advance"))
    if busy is None:
        return None
    return 100.0 * (r.window_s - busy) / r.window_s
