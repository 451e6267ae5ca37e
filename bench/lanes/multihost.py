"""Multi-host lane: ``MultiHostReplay.run_arrays``, latencies kept.

One job replays every host's trace on fresh mounts of a shared fabric:
``prepare_arrays`` builds the route and lookup tensors, one compiled scan
runs one global issue per step, and ``aggregate`` folds the per-step
streams into per-host results with the metrics bundle.  The three calls
are ``run_arrays`` line for line; the lane keeps the per-step
``(host, issue, done)`` streams that ``run_arrays`` drops, so that every
access's latency reaches the check.
"""

from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from bench.lib import compare, program, reference

MODULE = "_run_multi"     # the jitted runner, as the device trace names it
# the metrics bundle's shape, given to the program and the reference alike
METRICS = {"hist_buckets": 128, "window_ticks": 1_000_000, "num_windows": 64}


def setup(config: dict, traffic: dict) -> dict:
    if traffic["hosts"] != config["hosts"]:
        raise ValueError(f"traffic has {traffic['hosts']} hosts, the "
                         f"configuration {config['hosts']}")
    n = traffic["hosts"] * traffic["accesses"]
    return {"config": config, "accesses": n, "steps": n}


def run(ctx: dict, job: dict) -> dict:
    from repro.core.replay import MetricsSpec, MultiHostReplay

    cfg = ctx["config"]
    eng = MultiHostReplay(program.targets(cfg),
                          outstanding=cfg["outstanding"],
                          issue_overhead_ns=cfg["issue_overhead_ns"],
                          posted_writes=cfg["posted_writes"],
                          metrics=MetricsSpec(**METRICS))
    with TraceAnnotation("multihost.prepare_arrays"):
        prep = eng.prepare_arrays(job["addrs"], job["writes"])
    with TraceAnnotation("multihost.execute"):
        who, issues, dones, lens, size, _aux, bundle = \
            eng._execute_prepared(prep, 0)
    with TraceAnnotation("multihost.aggregate"):
        res = eng.aggregate(who, issues, dones, lens, size, 0)
        valid = np.arange(who.size) < int(np.asarray(lens).sum())
        lat = dones - issues
        per_host = [lat[valid & (who == h)] for h in range(len(lens))]
        metrics = bundle.to_jsonable()
    return {"latency": per_host,
            "summary": [(r.accesses, r.bytes_moved, r.elapsed_ticks,
                         r.sum_latency_ticks, r.end_tick)
                        for r in res.per_host] + [(res.elapsed_ticks,)],
            "metrics": metrics}


def reference_out(ctx: dict, job: dict, rng, tick_bits: int = 64) -> dict:
    return reference.hosts(ctx["config"], job, METRICS, tick_bits)


def check(ctx: dict, out: dict, ref: dict) -> dict:
    return compare.hosts(out, ref)


same = compare.same
