"""Chip time of the lane's replay program in the traced window, in
microseconds per sequential scan step (the job's steps: accesses for one
host or a sweep, the sum over hosts for a multi-host job)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.dropped or not ctx["steps"]:
        return None
    t = tr.module_time_s(ctx["module"])
    if t <= 0:
        return None
    return t / ctx["steps"] * 1e6
