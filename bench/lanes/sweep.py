"""Design-sweep lane: ``sweep.cache_design_sweep`` over the traffic's
design points.

One job replays one shared trace under every design point (a cache
capacity in frames and a policy) in one vmapped call, and ends with every
point's latencies, hit flags and summaries on the host.  The check replays
a sample of the points, drawn from the seed, on the reference.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from bench.lib import compare, program, reference

MODULE = "_run_cache_lanes"   # the jitted runner, as the device trace names it


def setup(config: dict, traffic: dict) -> dict:
    if config["hosts"] != 1 or traffic["hosts"] != 1:
        raise ValueError("the sweep lane replays one host")
    dp = traffic["design_points"]
    caps = [int(c) for c in dp["capacity_frames"]]
    policy = list(dp["policy"])
    if len(caps) != len(policy) or not set(policy) <= {"lru", "fifo"}:
        raise ValueError("design points need one lru/fifo policy per "
                         "capacity")
    n = traffic["accesses"]
    return {"config": config, "caps": caps,
            "policy": policy, "accesses": n * len(caps), "steps": n,
            "checked": traffic["checked_points_per_job"]}


def run(ctx: dict, job: dict) -> dict:
    from repro.core.replay import cache_design_sweep

    cfg = ctx["config"]
    with TraceAnnotation("sweep.cache_design_sweep"):
        out = cache_design_sweep(
            program.device(cfg), job["addrs"][0], job["writes"][0],
            capacity_frames=ctx["caps"],
            is_lru=[p == "lru" for p in ctx["policy"]],
            outstanding=cfg["outstanding"],
            issue_overhead_ns=cfg["issue_overhead_ns"],
            posted_writes=cfg["posted_writes"])
    return {"latency": list(out["latency_ticks"]),
            "hit": list(out["hit_flags"]),
            "summary": [(s, e) for s, e in zip(out["sum_latency_ticks"],
                                               out["elapsed_ticks"])]}


def reference_out(ctx: dict, job: dict, rng, tick_bits: int = 64) -> dict:
    """The reference at a sample of the design points, keyed by point."""
    points = sorted(int(k) for k in rng.choice(
        len(ctx["caps"]), size=ctx["checked"], replace=False))
    out = {"points": points, "latency": {}, "hit": {}, "summary": {}}
    for k in points:
        ref = reference.replay(
            ctx["config"], job["addrs"], job["writes"], tick_bits=tick_bits,
            cache_override={"capacity_bytes": ctx["caps"][k] * reference.PAGE,
                            "policy": ctx["policy"][k]})
        out["latency"][k] = ref["latency"][0]
        out["hit"][k] = ref["hit"][0]
        s = ref["summary"][0]
        out["summary"][k] = (s[3], ref["elapsed"])
    return out


def check(ctx: dict, out: dict, ref: dict) -> dict:
    pts = ref["points"]

    def pick(d, key):
        return [d[key][k] for k in pts]

    return {"latency": compare.latency(pick(out, "latency"),
                                       pick(ref, "latency")),
            "hits": compare.latency(pick(out, "hit"), pick(ref, "hit")),
            "summary": compare.fields(pick(out, "summary"),
                                      pick(ref, "summary"))}


same = compare.same
