"""Bi-block schedule: bucket executions per 1,000 sampled steps in the window."""


def read(r):
    per = r.per_step("bucket_executions")
    return None if per is None else 1000.0 * per
