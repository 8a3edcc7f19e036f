"""Bi-block schedule: walks per advance call in the window (the mean ``n`` of
the window's ``advance`` program spans)."""


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None:
        return None
    recs = spans.records(win.t_open, win.t_close)
    if recs is None:
        return None
    walks = [s.n for s in recs if s.name == "advance"]
    return float(sum(walks)) / len(walks) if walks else None
