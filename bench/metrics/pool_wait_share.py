"""Walk pool: share (%) of the window the engine's thread spends taking a
slot's walks from the pool (the preload wait and the remainder load) and
persisting walks to it, writer backpressure included (program spans
``pool.acquire`` and ``pool.push``)."""

NAMES = ("pool.acquire", "pool.push")


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None or r.window_s <= 0:
        return None
    seconds = spans.window(win.t_open, win.t_close, spans.thread_of("advance"))
    if seconds is None:
        return None
    return 100.0 * sum(seconds.get(k, 0.0) for k in NAMES) / r.window_s
