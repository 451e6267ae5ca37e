#!/usr/bin/env python3
"""Benchmark harness: simulated accesses per second of the fused replay lanes.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell names a configuration
(``bench/configs``), a traffic mix (``bench/traffic``) and, through them, a
lane (``bench/lanes``); per-layer metrics are read by ``bench/metrics``.
Everything is found by the names in ``BENCHMARK.json``.

A run refuses to start unless JAX's first device is a TPU and there are as
many as the cell asks for.  Set-up (``setup_s``, from process start) covers
JAX and TPU initialisation, the persistent compile cache at a fixed path
inside the checkout, a pool of 8 job traces built from ``--seed`` (job k
draws generator host indices ``k*H .. k*H+H-1``) and one warm job at the
cell's shapes.  The window then runs jobs back to back through the lane,
cycling through the pool, each ending with its results on the host, and
closes at the end of the first job that finishes after ``--seconds``.  The
rate is every simulated access of every job over the window's wall time.
With ``--trace 1`` a short window of its own is traced instead, and the
per-layer metrics are read from the trace.

After the window the outputs of every pool entry that ran are replayed on
the plain reference (``bench/lib/reference.py``) and compared exactly; the
numbers compared, each with its limit, are printed last on standard error
and last in the result, the JSON object on the last line of standard
output.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]     # bench package, program

import numpy as np  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
POOL = 8
# A traced window holds one job: its ~80-110 op events per scan step take
# minutes to write, and a few million more overflow the chip's trace
# buffers.
TRACE_JOBS = 1


class NoChip(RuntimeError):
    pass


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = ROOT / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: unknown workload {workload!r}; "
                         f"choose from {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def make_pool(traffic: dict, seed: int) -> list:
    from bench.lib import gen

    H, n = traffic["hosts"], traffic["accesses"]
    pool = []
    for k in range(POOL):
        cols = [gen.host_trace(traffic["generator"], seed, k * H + h, n)
                for h in range(H)]
        pool.append({"index": k,
                     "addrs": np.stack([a for a, _ in cols]),
                     "writes": np.stack([w for _, w in cols])})
    return pool


def chips(count: int) -> list:
    """The first ``count`` TPU devices; :class:`NoChip` otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX's first device is "
                     f"{devs[0].platform!r}")
    if len(devs) < count:
        raise NoChip(f"the cell needs {count} chips, JAX has {len(devs)}")
    return devs[:count]


def use_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_jobs(lane, ctx, pool, seconds: float, outs: dict, min_jobs: int = 1):
    """Jobs back to back until ``seconds`` have passed (at least
    ``min_jobs``); returns (jobs, wall seconds).  The first output of each
    pool entry is kept in ``outs``; repeats are kept to compare later."""
    from jax.profiler import TraceAnnotation

    jobs = 0
    t0 = time.perf_counter()
    while True:
        job = pool[jobs % POOL]
        with TraceAnnotation("bench.job"):
            out = lane.run(ctx, job)
        outs.setdefault(job["index"], []).append(out)
        jobs += 1
        if jobs >= min_jobs and time.perf_counter() - t0 >= seconds:
            return jobs, time.perf_counter() - t0


def verify(lane, ctx, pool, outs: dict, seed: int) -> tuple:
    """Exact comparison of the outputs with the reference: each number is
    a count of mismatches, with limit 0.  Returns (numbers, number of jobs
    whose output was wrong)."""
    totals = {}
    repeats = 0
    failed = set()
    for k, runs in sorted(outs.items()):
        rng = np.random.default_rng([seed, k])
        ref = lane.reference_out(ctx, pool[k], rng)
        for name, v in lane.check(ctx, runs[0], ref).items():
            totals[name] = totals.get(name, 0) + v
            if v:
                failed.add(k)
        for other in runs[1:]:
            if not lane.same(runs[0], other):
                repeats += 1
                failed.add(k)
    totals["repeats"] = repeats
    return totals, sum(len(outs[k]) for k in failed)


def traced_window(lane, ctx, pool, outs, devices):
    """Trace ``TRACE_JOBS`` jobs in a window of their own; returns (jobs,
    reduced trace)."""
    import jax

    from bench.lib import trace as tr

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(str(TRACE_DIR)):
        run_jobs(lane, ctx, pool, 0.0, outs, min_jobs=TRACE_JOBS)
    paths = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    reduced = tr.reduce(paths[-1], [d.id for d in devices]) if paths else None
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return TRACE_JOBS, reduced


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices: list, cache: bool = True, lane=None,
             traffic_override: dict | None = None) -> dict:
    """One run of a cell on ``devices`` (already checked); returns the
    result object.  Tests replace the cell's lane module (``lane``) or
    shrink its traffic (``traffic_override``)."""
    from bench.lib.clock import CompileClock

    bench, cell, config, traffic = load_cell(workload)
    traffic = {**traffic, **(traffic_override or {})}
    if cache:
        use_cache()
    lane = lane or load_module("lanes", traffic.get("lane", config["lane"]))
    ctx = lane.setup(config, traffic)
    pool = make_pool(traffic, seed)
    outs: dict = {}
    with CompileClock() as clock:
        run_jobs(lane, ctx, pool, 0.0, outs)              # warm job
        setup_s = time.perf_counter() - T0
        compile_s, before = clock.seconds, clock.events
        if trace:
            jobs, reduced = traced_window(lane, ctx, pool, outs, devices)
            window_s = reduced.window_s if reduced else None
        else:
            jobs, window_s = run_jobs(lane, ctx, pool, seconds, outs)
        in_window = clock.events - before
    print(f"run.py: {workload} seed={seed} jobs={jobs} window_s={window_s} "
          f"compiles_in_window={in_window}", file=sys.stderr, flush=True)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    if trace:
        metrics = {}
        rctx = {"trace": reduced, "steps": ctx["steps"] * jobs,
                "compile_s": compile_s, "module": lane.MODULE}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            v = load_module("metrics", m["name"]).read(rctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {
            "sim_accesses_per_s": {"value": jobs * ctx["accesses"] / window_s,
                                   "unit": "accesses/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    totals, failed = verify(lane, ctx, pool, outs, seed)
    checks = {**totals, "compiles_in_window": in_window}
    correct = all(x == 0 for x in checks.values())
    for name, val in checks.items():
        print(f"check {name}: {val} (limit 0)", file=sys.stderr)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct,
              "attempted": sum(len(r) for r in outs.values()),
              "failed": failed, "metrics": metrics,
              "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {name: {"value": val, "limit": 0}
                        for name, val in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _, cell, _, _ = load_cell(args.workload)
    try:
        devices = chips(cell["chips"])
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
