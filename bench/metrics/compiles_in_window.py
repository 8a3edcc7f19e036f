"""Device: programs compiled or loaded from the compile cache inside the
window (``jax.monitoring``); 0 when set-up warmed every shape."""


def read(r):
    return float(r.compiles_in_window)
