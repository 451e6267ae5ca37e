"""Pool lane: ``MultiHostReplay`` over the views of a logical-device pool.

One job builds a fresh pool from the configuration (a ``single_switch``
fabric, the one device, ``MemoryPool`` with ``ld_bytes`` so that host ``i``
owns LD ``i``) and replays every host's trace on it exactly as
``bench/lanes/multihost.py`` does: ``prepare_arrays``, one compiled scan of
one global issue per step, ``aggregate``, with the per-step
``(host, issue, done)`` streams kept so that every access's latency
reaches the check.
"""

from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from bench.lib import compare, program, reference_pool

MODULE = "_run_multi"     # the jitted runner, as the device trace names it
# the metrics bundle's shape, given to the program and the reference alike
METRICS = {"hist_buckets": 128, "window_ticks": 1_000_000, "num_windows": 64}


def views(config: dict) -> list:
    """One fresh pool as the configuration states it; its host views."""
    from repro.core.fabric import Fabric, MemoryPool

    fab = config["fabric"]
    if fab["kind"] != "single_switch" or fab["qos"]:
        raise ValueError("the pool lane builds a single_switch without QoS")
    h, d = config["hosts"], fab["devices"]
    fabric = Fabric.build("single_switch", num_hosts=h, num_devices=d,
                          bw_gbps=fab["bw_gbps"],
                          forward_ns=fab["forward_ns"],
                          rt_extra_ns=fab["rt_extra_ns"])
    pool = MemoryPool(fabric, {f"d{i}": program.device(config)
                               for i in range(d)},
                      ld_bytes=fab["ld_bytes"])
    return pool.views([f"h{i}" for i in range(h)])


def setup(config: dict, traffic: dict) -> dict:
    if traffic["hosts"] != config["hosts"]:
        raise ValueError(f"traffic has {traffic['hosts']} hosts, the "
                         f"configuration {config['hosts']}")
    views(config)          # a program without LD pools refuses here
    n = traffic["hosts"] * traffic["accesses"]
    return {"config": config, "accesses": n, "steps": n}


def run(ctx: dict, job: dict) -> dict:
    from repro.core.replay import MetricsSpec, MultiHostReplay

    cfg = ctx["config"]
    eng = MultiHostReplay(views(cfg), outstanding=cfg["outstanding"],
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
    return reference_pool.hosts(ctx["config"], job, METRICS, tick_bits)


def check(ctx: dict, out: dict, ref: dict) -> dict:
    return compare.hosts(out, ref)


same = compare.same
