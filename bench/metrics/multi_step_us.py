"""``step_us`` on the multi-host lane: chip time of ``_run_multi`` in the
traced window per sequential step (one global issue, summed over hosts).
Nothing on other lanes."""

from bench.metrics import step_us


def read(ctx):
    if ctx["module"] != "_run_multi":
        return None
    return step_us.read(ctx)
