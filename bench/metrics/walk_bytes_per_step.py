"""Walk pool: walk bytes written and read back per sampled step in the window."""


def read(r):
    return r.per_step("walk_bytes_written", "walk_bytes_read")
