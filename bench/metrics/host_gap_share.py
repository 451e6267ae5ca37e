"""Share, in %, of the traced window in which no XLA module runs on the
chip: the host's part of a job cycle (building the device stack and the
input tensors, fetching results, folding the metrics bundle)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.dropped or not tr.modules or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.module_s / tr.window_s)
