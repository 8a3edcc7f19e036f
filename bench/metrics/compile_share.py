"""Device: share (%) of the window the engine's thread spends building
programs (program spans ``compile:<function>``, recorded from JAX's
backend-compile event)."""


def read(r):
    win = r.out["window"]
    spans = getattr(win.stats, "spans", None)
    if spans is None or r.window_s <= 0:
        return None
    seconds = spans.window(win.t_open, win.t_close, spans.thread_of("advance"))
    if seconds is None:
        return None
    return 100.0 * sum(s for k, s in seconds.items() if k.startswith("compile:")) / r.window_s
