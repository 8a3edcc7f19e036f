"""Host-device call: share (%) of the window the engine's thread spends
copying the advance's outputs back and writing the corpus (program spans
``advance.fetch`` and ``advance.record``)."""

NAMES = ("advance.fetch", "advance.record")


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None or r.window_s <= 0:
        return None
    seconds = spans.window(win.t_open, win.t_close, spans.thread_of("advance"))
    if seconds is None:
        return None
    return 100.0 * sum(seconds.get(k, 0.0) for k in NAMES) / r.window_s
