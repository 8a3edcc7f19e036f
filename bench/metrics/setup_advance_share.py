"""Device advance: share (%) of set-up spent inside advance calls (program
span ``advance`` over ``[t_open - setup_s, t_open]``): the initialization
stage and the warm-up supersteps, compiles included."""


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None or r.setup_s <= 0:
        return None
    t_a = win.t_open - r.setup_s
    seconds = spans.window(t_a, win.t_open, spans.thread_of("advance"))
    if seconds is None:
        return None
    return 100.0 * seconds.get("advance", 0.0) / r.setup_s
