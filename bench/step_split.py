"""One cell's step split by named scope, measured by hand on the chip.

From the root of a checkout::

    python3 -m bench.step_split --workload tableI.zipf --seed 7

runs one warm job of the cell, one job with the profiler off and one
traced, then one more with the scope recording on, and prints the split
(``bench/lib/step_parts.py``, per step, in microseconds) with the wall
time of each job, as JSON on the last line of standard output.  A program
without named scopes gives the timings and no split.

This stands beside ``bench/run.py`` until its ``--trace 1`` runs record
the scope map and keep the asynchronous copies themselves; then the
per-layer metrics read the split and this script goes.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

from bench import run
from bench.lib import step_parts as sp
from bench.lib import trace as tr

TRACE_DIR = run.ROOT / ".bench_trace" / "step_split"


def _timed(lane, ctx, job) -> float:
    t0 = time.perf_counter()
    lane.run(ctx, job)
    return time.perf_counter() - t0


def measure(workload: str, seed: int, devices, cache: bool = True,
            traffic_override: dict | None = None) -> dict:
    """One cell's jobs as the module docstring says; returns the split
    (per step, in microseconds) and the wall times."""
    import jax

    _, cell, config, traffic = run.load_cell(workload)
    traffic = {**traffic, **(traffic_override or {})}
    if cache and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        run.use_cache()
    lane = run.load_module("lanes", traffic.get("lane", config["lane"]))
    ctx = lane.setup(config, traffic)
    pool = run.make_pool(traffic, seed)
    job = pool[0]
    out = {"workload": workload, "seed": seed,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices)},
           "warm_job_s": _timed(lane, ctx, job),
           "job_s": _timed(lane, ctx, job)}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(str(TRACE_DIR)):
        with jax.profiler.TraceAnnotation(tr.JOB_SPAN):
            out["traced_job_s"] = _timed(lane, ctx, job)
    out["trace_session_s"] = time.perf_counter() - t0
    smap, strict, out["scope_job_s"] = sp.scope_maps(lane, ctx, job)
    paths = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    t0 = time.perf_counter()
    ids = [d.id for d in devices]
    reduced = tr.reduce(paths[-1], ids) if paths else None
    copies = sp.load_copies(paths[-1], ids) if paths else []
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    out["scope_map_instructions"] = len(smap or ())
    steps = ctx["steps"]
    if reduced is None or reduced.dropped or not reduced.modules:
        out["parts"] = None
        return out
    parts = sp.step_parts(reduced, copies, lane.MODULE, smap)
    us = 1e6 / steps
    out["parts"] = {
        "step_us": reduced.module_time_s(lane.MODULE) * us,
        "charged_us": {k: v * us for k, v in parts["charged"].items()},
        "busy_us": {k: v * us for k, v in parts["busy"].items()},
        "dma_wait_us": parts["dma_wait"] * us,
        "ops_per_step": parts["ops"] / steps,
        "host_gap_share": 100.0 * (1 - reduced.module_s / reduced.window_s),
        "device_idle_share": 100.0 * (1 - reduced.busy_s
                                      / reduced.window_s),
        "no_module_s": sp.idle_by_span(reduced)}
    if strict is not None:
        raw = sp.step_parts(reduced, copies, lane.MODULE, strict)
        out["parts"]["no_op_name_share"] = \
            100.0 * raw["charged"].get(sp.UNNAMED, 0.0) / raw["module"]
        out["parts"]["unnamed_share"] = \
            100.0 * parts["charged"].get(sp.UNNAMED, 0.0) / parts["module"]
    out["reduce_s"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", type=Path, default=None,
                    help="also write the JSON here")
    args = ap.parse_args(argv)
    _, cell, _, _ = run.load_cell(args.workload)
    try:
        devices = run.chips(cell["chips"])
    except run.NoChip as e:
        print(f"step_split: {e}", file=sys.stderr)
        return 2
    res = measure(args.workload, args.seed, devices)
    line = json.dumps(res)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
