"""``device_idle_share`` on the multi-host lane: share, in %, of the traced
window in which no leaf XLA op runs while ``_run_multi`` is the lane's
program.  Nothing on other lanes."""

from bench.metrics import device_idle_share


def read(ctx):
    if ctx["module"] != "_run_multi":
        return None
    return device_idle_share.read(ctx)
