"""Block store: graph bytes loaded (full blocks and on-demand rows) per
sampled step in the window."""


def read(r):
    return r.per_step("block_bytes", "ondemand_bytes")
