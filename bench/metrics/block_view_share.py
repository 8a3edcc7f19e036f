"""Block store: share (%) of the window the engine's thread spends inside the
store's view calls: full, partial, extended and gathered views and prefetch
scheduling (program spans ``blocks.get_view``, ``blocks.partial_view``,
``blocks.extend_view``, ``blocks.gather_view``, ``blocks.schedule``)."""

NAMES = (
    "blocks.get_view",
    "blocks.partial_view",
    "blocks.extend_view",
    "blocks.gather_view",
    "blocks.schedule",
)


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None or r.window_s <= 0:
        return None
    seconds = spans.window(win.t_open, win.t_close, spans.thread_of("advance"))
    if seconds is None:
        return None
    return 100.0 * sum(seconds.get(k, 0.0) for k in NAMES) / r.window_s
