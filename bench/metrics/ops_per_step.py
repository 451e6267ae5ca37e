"""Leaf XLA ops launched inside the lane's replay program in the traced
window, per sequential scan step (the divisor of ``step_us``): the count
of launched work, which sets the floor of the launch gaps between ops.
An op counts where it starts inside one of the program's executions,
clipped to the window as ``step_us`` clips them."""

import bisect


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.dropped or not tr.ops or not ctx["steps"]:
        return None
    count = 0
    for c, chip in enumerate(tr.modules):
        starts = sorted(s for s, _, _ in tr.ops[c]) if c < len(tr.ops) \
            else []
        for s, e, name in chip:
            if ctx["module"] not in name or e <= tr.lo or s >= tr.hi:
                continue
            count += (bisect.bisect_left(starts, min(e, tr.hi))
                      - bisect.bisect_left(starts, max(s, tr.lo)))
    if not count:
        return None
    return count / len(tr.modules) / ctx["steps"]
