"""Share, in %, of the traced window in which no leaf XLA op runs on the
chip: 1 - (union of leaf op intervals) / window.  Inside one long scan the
gaps between a step's small ops count as idle, with the host's gaps."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.dropped or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
