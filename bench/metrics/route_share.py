"""Bi-block schedule: share (%) of the window the engine's thread spends
retiring walks, routing them by Alg. 2 and splitting slots into buckets
(program spans ``slot.route`` and ``buckets.split`` on that thread; splits
the walk pool's writer runs ahead are not counted)."""

NAMES = ("slot.route", "buckets.split")


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None or r.window_s <= 0:
        return None
    seconds = spans.window(win.t_open, win.t_close, spans.thread_of("advance"))
    if seconds is None:
        return None
    return 100.0 * sum(seconds.get(k, 0.0) for k in NAMES) / r.window_s
