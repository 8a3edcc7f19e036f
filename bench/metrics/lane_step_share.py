"""Device advance: share (%) of the advance loop's lanes that took a step in
the window: the window's sampled steps over the lanes the loop ran, summed
over its iterations (the ``n`` of the window's ``advance.fetch`` program
spans).  Nothing where the program does not count its lanes there."""


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None:
        return None
    recs = spans.records(win.t_open, win.t_close)
    if recs is None:
        return None
    lanes = sum(s.n for s in recs if s.name == "advance.fetch")
    if lanes <= 0:
        return None
    return 100.0 * r.counters["steps_sampled"] / lanes
