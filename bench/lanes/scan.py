"""Single-host lane: ``ReplayEngine.run_arrays`` with the metrics bundle.

One job replays one host's trace on a fresh device, one ``lax.scan`` step
per access, and ends with the latencies, the summary and the forced
metrics bundle on the host.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from bench.lib import compare, program, reference

MODULE = "_run_stack"     # the jitted runner, as the device trace names it
# the metrics bundle's shape, given to the program and the reference alike
METRICS = {"hist_buckets": 128, "window_ticks": 1_000_000, "num_windows": 64}


def setup(config: dict, traffic: dict) -> dict:
    if config["hosts"] != 1 or traffic["hosts"] != 1:
        raise ValueError("the scan lane replays one host")
    return {"config": config, "accesses": traffic["accesses"],
            "steps": traffic["accesses"]}


def run(ctx: dict, job: dict) -> dict:
    from repro.core.replay import MetricsSpec, ReplayEngine

    cfg = ctx["config"]
    with TraceAnnotation("scan.run_arrays"):
        res = ReplayEngine(
            program.device(cfg), outstanding=cfg["outstanding"],
            issue_overhead_ns=cfg["issue_overhead_ns"],
            posted_writes=cfg["posted_writes"],
            metrics=MetricsSpec(**METRICS)).run_arrays(job["addrs"][0],
                                                       job["writes"][0])
    with TraceAnnotation("scan.bundle"):
        bundle = res.metrics.to_jsonable()
    return {"latency": [res.latency_ticks],
            "summary": [(res.accesses, res.bytes_moved, res.elapsed_ticks,
                         res.sum_latency_ticks, res.end_tick),
                        (res.elapsed_ticks,)],
            "metrics": bundle}


def reference_out(ctx: dict, job: dict, rng, tick_bits: int = 64) -> dict:
    return reference.hosts(ctx["config"], job, METRICS, tick_bits)


def check(ctx: dict, out: dict, ref: dict) -> dict:
    return compare.hosts(out, ref)


same = compare.same
