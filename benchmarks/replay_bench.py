"""Fused-replay throughput: every engine lane on 200k-access traces.

Three device classes, every fast lane the repo has, one JSON artifact:

* ``dram`` / ``pmem`` — python vs scan vs blocked scan (block-size sweep)
  vs the log-depth associative lane (``repro.core.replay.assoc``);
* ``cxl-ssd-cache`` — python vs scan vs blocked scan vs the Pallas kernel
  (interpret mode on CPU);
* ``multihost`` — cached CXL-SSD behind a shared fabric at 2 and 4 hosts
  (private per-host mounts), interpreted ``MultiHostDriver`` vs the fused
  ``MultiHostReplay`` stacked-state scan, exactness asserted per lane;
* ``scan_metrics`` — each device's scan re-run with telemetry enabled
  (``metrics=MetricsSpec()``): records the p50/p99 and counter summaries
  plus ``overhead_vs_scan``, the relative cost of observability over the
  bare scan, timed interleaved with it (CI-guarded at <10%);
* ``fleet`` — 64 hosts on a 4-pod datacenter fabric, >=100k accesses
  synthesized on device by the jnp workload twin, the ``shard_map``
  sharded lane exactness-flagged against the unsharded fused lane and
  the interpreted driver at the recorded scale (derived-only; re-record
  alone with ``--lanes fleet``).

Methodology (the numbers this file writes are compared across PRs):

* the trace is converted to arrays ONCE, outside every timed region — the
  lanes are timed on their natural inputs (python on the tuple list it
  interprets, the compiled lanes on arrays);
* compiled lanes are timed **steady-state**: compile+warm on the first
  call, then the minimum of ``REPEATS`` timed calls; compile time is
  reported separately (``compile_seconds``), never mixed into throughput;
* every scan/assoc lane's result is asserted tick-identical to the
  interpreted driver and the bit is recorded per lane
  (``tick_exact_vs_python``); the pallas lane records its own contract
  (``decisions_exact`` vs the cache oracle + the associative latency
  reconstruction cross-check);
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Tuple

import numpy as np

from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.devices import make_device
from repro.core.replay import AssocReplayEngine, MetricsSpec, ReplayEngine
from repro.core.workloads.driver import TraceDriver

Row = Tuple[str, float, str]

N = 200_000
REPEATS = 3
CACHE_FRAMES = 256          # 1 MB DRAM cache
FOOTPRINT_PAGES = 1024      # 4 MB working set -> ~45% hit rate
WRITE_FRAC = 0.3
BLOCK_SIZES = (8, 32)       # blocked-scan sweep
TARGETS = {"dram": 20.0, "pmem": 20.0, "cxl-ssd-cache": 10.0}
MULTI_HOSTS = (2, 4)        # multihost lane: cached CXL-SSD x host count
MULTI_N = 50_000            # accesses per host
MULTI_TARGET = 5.0          # fused speedup floor (CI-guarded)
OUT_JSON = os.path.join(os.path.dirname(__file__), os.pardir, "results",
                        "BENCH_replay.json")


def _mk_device(name: str):
    if name == "cxl-ssd-cache":
        return make_device(name, cache_cfg=DRAMCacheConfig(
            capacity_bytes=CACHE_FRAMES * 4096))
    return make_device(name)


def _trace(n: int):
    rng = np.random.default_rng(3)
    pages = rng.integers(0, FOOTPRINT_PAGES, n)
    addrs = pages * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < WRITE_FRAC
    return [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]


def _exact(py, rp) -> bool:
    return (py.sum_latency_ticks == rp.sum_latency_ticks
            and py.elapsed_ticks == rp.elapsed_ticks
            and py.end_tick == rp.end_tick)


def _steady(fn):
    """(first-call seconds, steady-state seconds, last result): compile+warm
    once, then min over REPEATS timed calls."""
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return first, best, out


def _lane(py, py_s, fn, **extra):
    first, steady, rp = _steady(fn)
    exact = _exact(py, rp)
    assert exact, "fast lane diverged from the interpreted driver"
    return {
        "steady_seconds": steady,
        "compile_seconds": max(0.0, first - steady),
        "acc_per_sec": N / steady,
        "speedup_vs_python": py_s / steady,
        "tick_exact_vs_python": bool(exact),
        **extra,
    }


def _bench_device(name: str, trace, addrs, writes) -> dict:
    target = TARGETS[name]
    t0 = time.perf_counter()
    py = TraceDriver(_mk_device(name)).run(trace)
    py_s = time.perf_counter() - t0
    lanes = {"python": {"seconds": py_s, "acc_per_sec": N / py_s}}

    scan = ReplayEngine(_mk_device(name))
    lanes["scan"] = _lane(py, py_s, lambda: scan.run_arrays(addrs, writes))

    # in-scan telemetry lane: same scan with the MetricsSpec carry; records
    # the percentile/counter summary and its cost over the bare scan
    # (CI-guarded at <10%)
    meng = ReplayEngine(_mk_device(name), metrics=MetricsSpec())
    t0 = time.perf_counter()
    rp = meng.run_arrays(addrs, writes)
    first = time.perf_counter() - t0
    # the overhead is a ratio of two nearly-equal wall times, so time the
    # two programs interleaved in one loop (same scheduler/thermal window)
    # rather than reusing the scan lane's earlier window
    bare = steady = float("inf")
    for _ in range(2 * REPEATS):
        t0 = time.perf_counter()
        scan.run_arrays(addrs, writes)
        bare = min(bare, time.perf_counter() - t0)
        t0 = time.perf_counter()
        rp = meng.run_arrays(addrs, writes)
        steady = min(steady, time.perf_counter() - t0)
    exact = _exact(py, rp)
    assert exact, "metrics lane diverged from the interpreted driver"
    mb = rp.metrics
    lanes["scan_metrics"] = {
        "steady_seconds": steady,
        "compile_seconds": max(0.0, first - steady),
        "acc_per_sec": N / steady,
        "speedup_vs_python": py_s / steady,
        "tick_exact_vs_python": bool(exact),
        "overhead_vs_scan": steady / bare - 1.0,
        "p50_ticks": mb.percentile_ticks(50),
        "p99_ticks": mb.percentile_ticks(99),
        "hit_rate": mb.hit_rate,
        "write_amplification": mb.write_amplification,
        "counters": {k: int(v) for k, v in mb.media[0].items()},
    }

    for b in BLOCK_SIZES:
        eng = ReplayEngine(_mk_device(name), block_size=b)
        lanes[f"scan_b{b}"] = _lane(py, py_s,
                                    lambda: eng.run_arrays(addrs, writes),
                                    block_size=b)

    if name in ("dram", "pmem"):
        eng = AssocReplayEngine(_mk_device(name))
        lanes["assoc"] = _lane(py, py_s,
                               lambda: eng.run_arrays(addrs, writes))
        lanes["assoc"]["sweeps"] = eng._last_sweeps

    if name == "cxl-ssd-cache":
        from repro.core.cache.trace_sim import TraceCacheSim
        from repro.core.replay.pallas_engine import run_pallas

        dev = _mk_device(name)
        first, steady, rp = _steady(
            lambda: run_pallas(dev, addrs, writes, validate=True))
        hits, _, _ = TraceCacheSim(num_sets=1, ways=CACHE_FRAMES,
                                   policy="lru").run(
            (addrs // 4096).astype(np.int32), writes)
        decisions = bool((np.asarray(hits) == rp.hit_flags).all())
        lanes["pallas"] = {
            "steady_seconds": steady,
            "compile_seconds": max(0.0, first - steady),
            "acc_per_sec": N / steady,
            "speedup_vs_python": py_s / steady,
            "decisions_exact": decisions,
            "note": "analytic latency contract; interpret mode on CPU, "
                    "validated against the associative reconstruction",
        }

    best = max(v["speedup_vs_python"] for k, v in lanes.items()
               if v.get("tick_exact_vs_python"))
    lanes["best_exact_speedup"] = best
    lanes["meets_target"] = best >= target
    return lanes


def _multi_targets(nh: int):
    from repro.core.fabric import Fabric

    fab = Fabric.build("two_level", num_hosts=nh, num_devices=nh,
                       num_leaves=2)
    return [fab.mount(f"h{i}", f"d{i}", _mk_device("cxl-ssd-cache"))
            for i in range(nh)]


def _multi_exact(py, rp) -> bool:
    return (py.elapsed_ticks == rp.elapsed_ticks
            and all(a.elapsed_ticks == b.elapsed_ticks
                    and a.sum_latency_ticks == b.sum_latency_ticks
                    and a.end_tick == b.end_tick
                    for a, b in zip(py.per_host, rp.per_host)))


def _bench_multihost(nh: int) -> dict:
    """Cached CXL-SSD x ``nh`` hosts: the stacked-state multi-host scan
    (per-host private cache over per-host flash) vs the interpreted
    interleaving driver, on one shared two-level fabric."""
    from repro.core.replay import MultiHostReplay
    from repro.core.workloads.driver import MultiHostDriver

    rng = np.random.default_rng(7)
    traces = []
    for h in range(nh):
        pages = rng.integers(0, FOOTPRINT_PAGES, MULTI_N)
        addrs = pages * 4096 + rng.integers(0, 64, MULTI_N) * 64
        writes = rng.random(MULTI_N) < WRITE_FRAC
        traces.append([(int(a), 64, bool(w))
                       for a, w in zip(addrs, writes)])
    n_total = nh * MULTI_N
    t0 = time.perf_counter()
    py = MultiHostDriver(_multi_targets(nh)).run(traces)
    py_s = time.perf_counter() - t0
    block = BLOCK_SIZES[0]
    first, steady, rp = _steady(
        lambda: MultiHostReplay(_multi_targets(nh),
                                block_size=block).run(traces))
    exact = _multi_exact(py, rp)
    assert exact, "multi-host fused replay diverged from the driver"
    return {
        "hosts": nh,
        "accesses_per_host": MULTI_N,
        "block_size": block,
        "python_seconds": py_s,
        "steady_seconds": steady,
        "compile_seconds": max(0.0, first - steady),
        "acc_per_sec": n_total / steady,
        "speedup_vs_python": py_s / steady,
        "tick_exact_vs_python": bool(exact),
    }


FAULT_N = 20_000            # fault-injected lane: derived-metrics size

# streaming lane: replay straight from an on-disk columnar TraceStore in
# O(chunk) input memory (ISSUE 8 tentpole) — 1M+ accesses, two chunk
# sizes, exactness asserted against the one-shot scan
STREAM_N = 1_200_000
STREAM_CHUNKS = (32_768, 131_072)
STREAM_DEPTH = 2            # prefetch windows in flight


def _stream_trace_arrays(n: int):
    rng = np.random.default_rng(5)
    pages = rng.integers(0, FOOTPRINT_PAGES, n)
    addrs = pages * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < WRITE_FRAC
    return addrs.astype(np.int64), writes


def collect_streaming_derived(accesses: int = 2_000,
                              chunk_sizes=(64, 256)) -> dict:
    """Derived (simulated) results of the streaming lane — a pure function
    of the seeds: exactness bits, metrics parity, and the *analytic*
    memory model (``(depth + 1) * chunk * row_bytes``).  No wall-clock or
    measured-peak numbers leak in, so the JSON is byte-identical across
    runs (CI-guarded)."""
    import tempfile
    from pathlib import Path

    from repro.core.replay import replay_stream
    from repro.data.trace_store import TraceStore

    addrs, writes = _stream_trace_arrays(accesses)
    out = {"n_accesses": accesses, "prefetch_depth": STREAM_DEPTH}
    dev = _mk_device("dram")
    base = ReplayEngine(dev, metrics=MetricsSpec()).run_arrays(
        addrs, writes, return_latencies=False)
    bm = base.metrics.to_jsonable()
    out["oneshot"] = {"sum_latency_ticks": int(base.sum_latency_ticks),
                      "end_tick": int(base.end_tick)}
    with tempfile.TemporaryDirectory() as td:
        store = TraceStore.write(Path(td) / "bench.store", addrs, writes)
        out["trace_input_bytes"] = store.n * store.row_bytes
        for ch in chunk_sizes:
            stats = {}
            rp = replay_stream(store, _mk_device("dram"), chunk_size=ch,
                               prefetch_depth=STREAM_DEPTH,
                               metrics=MetricsSpec(),
                               return_latencies=False, stats=stats)
            out[f"chunk_{ch}"] = {
                "chunk_size": ch,
                "chunks": stats["chunks"],
                "chunk_input_bytes": stats["chunk_input_bytes"],
                "peak_input_bound_bytes": stats["peak_input_bound_bytes"],
                "tick_exact_vs_oneshot": bool(_exact(base, rp)),
                "metrics_equal": rp.metrics.to_jsonable() == bm,
            }
    return out


def _bench_streaming() -> dict:
    """Wall-clock streaming lane: ``STREAM_N`` accesses replayed from an
    on-disk store at each chunk size, with the analytic O(chunk) input
    bound and the measured prefetch high-water mark recorded (peak RSS is
    informational — it reflects everything the process ever touched)."""
    import resource
    import tempfile
    from pathlib import Path

    from repro.core.replay import replay_stream
    from repro.data.trace_store import TraceStore

    addrs, writes = _stream_trace_arrays(STREAM_N)
    base = ReplayEngine(_mk_device("dram")).run_arrays(
        addrs, writes, return_latencies=False)
    lane = {"n_accesses": STREAM_N, "device": "dram",
            "prefetch_depth": STREAM_DEPTH,
            "oneshot_sum_latency_ticks": int(base.sum_latency_ticks),
            "oneshot_end_tick": int(base.end_tick),
            "chunks": {}}
    with tempfile.TemporaryDirectory() as td:
        store = TraceStore.write(Path(td) / "bench.store", addrs, writes)
        lane["trace_input_bytes"] = store.n * store.row_bytes
        for ch in STREAM_CHUNKS:
            dev = _mk_device("dram")
            stats = {}
            first, steady, rp = _steady(
                lambda: replay_stream(store, dev, chunk_size=ch,
                                      prefetch_depth=STREAM_DEPTH,
                                      return_latencies=False, stats=stats))
            exact = _exact(base, rp)
            assert exact, "streamed replay diverged from one-shot"
            lane["chunks"][str(ch)] = {
                "chunk_size": ch,
                "steady_seconds": steady,
                "compile_seconds": max(0.0, first - steady),
                "ns_per_access": steady * 1e9 / STREAM_N,
                "acc_per_sec": STREAM_N / steady,
                "tick_exact_vs_oneshot": bool(exact),
                "chunk_input_bytes": stats["chunk_input_bytes"],
                "peak_input_bound_bytes": stats["peak_input_bound_bytes"],
                "peak_buffered_bytes": stats["peak_buffered_bytes"],
            }
    lane["peak_rss_kb"] = int(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    lane["derived"] = collect_streaming_derived()
    return lane


def collect_fault_derived(accesses: int = FAULT_N) -> dict:
    """Derived (simulated) results of the fault-injected replay lanes — a
    pure function of the seeds: fault counters, latency totals, and the
    python-vs-scan exactness bits.  No wall-clock numbers leak in, so the
    JSON is byte-identical across runs (CI-guarded)."""
    from repro.core.fabric import Fabric
    from repro.core.faults import FaultConfig, FaultPlan, install
    from repro.core.replay import MultiHostReplay
    from repro.core.workloads.driver import MultiHostDriver

    out = {"n_accesses": accesses}

    # transport faults (link CRC retries + a down window + poison) on an
    # ECMP spine-leaf DRAM mount, single host
    def mk_mount():
        fab = Fabric.build("spine_leaf", num_hosts=2, num_devices=2,
                           num_leaves=2, num_spines=2, ecmp=True)
        return fab.mount("h0", "d0", _mk_device("dram"))

    cfg = FaultConfig(link_retry_rate=0.2, link_retry_max=2,
                      down_links=(("s0", "sp0", accesses // 4,
                                   accesses // 2),),
                      poison_rate=0.05)
    trace = _trace(accesses)
    addrs = np.asarray([a for a, _, _ in trace], np.int64)
    writes = np.asarray([w for _, _, w in trace], bool)
    t1 = mk_mount()
    install(FaultPlan(cfg, seed=11), [t1])
    py = TraceDriver(t1, metrics=MetricsSpec()).run(trace)
    t2 = mk_mount()
    install(FaultPlan(cfg, seed=11), [t2])
    rp = ReplayEngine(t2, metrics=MetricsSpec()).run_arrays(addrs, writes)
    js = rp.metrics.to_jsonable()
    out["transport@spine_leaf_ecmp"] = {
        "tick_exact_vs_python": _exact(py, rp),
        "metrics_equal": py.metrics.to_jsonable() == js,
        "faults": js["faults"],
        "sum_latency_ticks": int(rp.sum_latency_ticks),
        "end_tick": int(rp.end_tick),
    }

    # NAND read retries on a 2-host cached CXL-SSD fabric (transport
    # faults on multi-host mounts are exercised by the availability lane)
    def mk_mh():
        fab = Fabric.build("two_level", num_hosts=2, num_devices=2,
                           num_leaves=2)
        return [fab.mount(f"h{i}", f"d{i}", _mk_device("cxl-ssd-cache"))
                for i in range(2)]

    cfgn = FaultConfig(nand_read_retry_rate=0.3)
    rng = np.random.default_rng(13)
    traces = []
    for _ in range(2):
        pages = rng.integers(0, FOOTPRINT_PAGES, accesses // 2)
        a = pages * 4096 + rng.integers(0, 64, accesses // 2) * 64
        w = rng.random(accesses // 2) < WRITE_FRAC
        traces.append([(int(x), 64, bool(y)) for x, y in zip(a, w)])
    tm = mk_mh()
    install(FaultPlan(cfgn, seed=11), tm)
    pym = MultiHostDriver(tm, metrics=MetricsSpec()).run(traces)
    tm = mk_mh()
    install(FaultPlan(cfgn, seed=11), tm)
    rpm = MultiHostReplay(tm, metrics=MetricsSpec()).run(traces)
    jm = rpm.metrics.to_jsonable()
    out["nand@multihost_x2"] = {
        "tick_exact_vs_python": _multi_exact(pym, rpm),
        "metrics_equal": pym.metrics.to_jsonable() == jm,
        "faults": jm["faults"],
        "elapsed_ticks": int(rpm.elapsed_ticks),
    }
    return out


# availability sweep: fused multi-host replay under transport faults,
# one vmapped lane per fault seed (ISSUE 9 tentpole) — derived-only, so
# the JSON is byte-identical across runs (CI-guarded)
AVAIL_SEEDS = 16
AVAIL_HOSTS = (4, 8)
AVAIL_N = 320               # accesses per host


def collect_availability_derived(host_counts=AVAIL_HOSTS,
                                 n_seeds: int = AVAIL_SEEDS,
                                 accesses: int = AVAIL_N) -> dict:
    """Fleet-scale availability under transport faults — a pure function
    of the seeds: per fault seed the pooled tail latencies, the
    tick-windowed reachable-fraction curve, and the fault counters, on a
    spine-leaf ECMP fabric at each host count.  Every seed lane of the
    vmapped sweep is asserted tick-exact against the interpreted
    ``MultiHostDriver``; no wall-clock numbers leak in, so the JSON is
    byte-identical across runs (CI-guarded)."""
    from repro.core.fabric import Fabric
    from repro.core.faults import FaultConfig, FaultPlan, install
    from repro.core.replay.sweep import fault_seed_sweep
    from repro.core.workloads.driver import MultiHostDriver

    fcfg = FaultConfig(link_retry_rate=0.15, link_retry_max=2,
                       down_links=(("s0", "sp0", accesses // 4,
                                    (3 * accesses) // 4),))
    out = {
        "n_seeds": n_seeds,
        "accesses_per_host": accesses,
        "fault_config": {
            "link_retry_rate": 0.15, "link_retry_max": 2,
            "down_links": [["s0", "sp0", accesses // 4,
                            (3 * accesses) // 4]],
        },
    }
    for nh in host_counts:
        def mk(seed, nh=nh):
            fab = Fabric.build("spine_leaf", num_hosts=nh, num_devices=nh,
                               num_leaves=2, num_spines=2, ecmp=True)
            tgts = [fab.mount(f"h{i}", f"d{i}", _mk_device("dram"))
                    for i in range(nh)]
            install(FaultPlan(fcfg, seed=seed), tgts)
            return tgts

        rng = np.random.default_rng(17)
        traces = []
        for _ in range(nh):
            pages = rng.integers(0, FOOTPRINT_PAGES, accesses)
            a = pages * 4096 + rng.integers(0, 64, accesses) * 64
            w = rng.random(accesses) < WRITE_FRAC
            traces.append([(int(x), 64, bool(y)) for x, y in zip(a, w)])
        seeds = list(range(n_seeds))
        lanes = fault_seed_sweep(mk, traces, seeds, outstanding=8)
        exact = True
        for lane in lanes:
            py = MultiHostDriver(mk(lane["seed"]), outstanding=8).run(traces)
            exact = exact and _multi_exact(py, lane["result"])
        assert exact, "availability sweep lane diverged from the driver"
        p = lambda lat, q: int(np.percentile(lat, q, method="higher"))
        per_seed = {
            str(lane["seed"]): {
                "p50_ticks": p(lane["latency_ticks"], 50),
                "p99_ticks": p(lane["latency_ticks"], 99),
                "max_ticks": int(lane["latency_ticks"].max()),
                "degraded_fraction":
                    lane["availability"]["degraded_fraction"],
                "failovers": lane["availability"]["failovers"],
                "failover_latency_penalty_ticks":
                    lane["availability"]["failover_latency_penalty_ticks"],
                "time_in_degraded_windows_ticks":
                    lane["availability"]["time_in_degraded_windows_ticks"],
                "link_retries": lane["fault_stats"]["link_retries"],
                "elapsed_ticks": int(lane["result"].elapsed_ticks),
            } for lane in lanes}
        av0 = lanes[0]["availability"]
        W = av0["num_windows"]
        # seed-averaged availability curve on the shared window axis
        curve = {}
        for w in range(W):
            fracs = [lane["availability"]["windows"].get(str(w))
                     for lane in lanes]
            fracs = [f["reachable_fraction"] for f in fracs if f]
            if fracs:
                curve[str(w)] = round(sum(fracs) / len(fracs), 9)
        p99s = [v["p99_ticks"] for v in per_seed.values()]
        degf = [v["degraded_fraction"] for v in per_seed.values()]
        out[f"hosts_x{nh}"] = {
            "hosts": nh,
            "tick_exact_vs_python": bool(exact),
            "window_ticks": av0["window_ticks"],
            "num_windows": W,
            "seeds": per_seed,
            "availability_curve": curve,
            "tail_p99_ticks": {"min": min(p99s), "max": max(p99s),
                               "mean": round(sum(p99s) / len(p99s), 6)},
            "degraded_fraction": {"min": min(degf), "max": max(degf),
                                  "mean": round(sum(degf) / len(degf), 9)},
        }
    return out


# fleet lane: rack-scale sharded replay (ISSUE 10 tentpole) — 64 hosts on
# a 4-pod datacenter fabric, >=100k accesses synthesized ON DEVICE by the
# jnp workload twin, the shard_map lane exactness-flagged against the
# unsharded fused lane (and the unsharded lane against the interpreted
# driver) at the full recorded scale.  Derived-only, so the JSON is
# byte-identical across runs (CI-guarded).
FLEET_HOSTS = 64
FLEET_N = 1_600             # accesses per host -> 102_400 total
FLEET_PODS = 4
FLEET_SEED = 23


def collect_fleet_derived(num_hosts: int = FLEET_HOSTS,
                          accesses: int = FLEET_N,
                          num_pods: int = FLEET_PODS,
                          check_python: bool = True) -> dict:
    """Derived (simulated) results of the rack-scale sharded fleet lane —
    a pure function of the workload seed: per-lane exactness bits, the
    mesh shape, fleet-pooled tail percentiles and the media counters.  No
    wall-clock numbers leak in, so the JSON is byte-identical across runs
    (CI-guarded); CI re-runs it scaled down and double-checks the bits."""
    from jax import enable_x64

    from repro.core.fabric import Fabric
    from repro.core.replay import (MetricsSpec, MultiHostReplay,
                                   ShardedMultiHostReplay)
    from repro.core.workloads.driver import MultiHostDriver
    from repro.data import WorkloadSpec, host_trace_jnp, make_traces

    spec = WorkloadSpec("zipfian", num_pages=FOOTPRINT_PAGES, zipf_s=1.1,
                        write_frac=WRITE_FRAC)
    # on-device synthesis: the traced twin builds every host column as a
    # pure function of (seed, host, i) — no python per-access objects
    with enable_x64(True):
        cols = [host_trace_jnp(spec, FLEET_SEED, h, accesses)
                for h in range(num_hosts)]
        addrs = np.stack([np.asarray(a, np.int64) for a, _ in cols])
        writes = np.stack([np.asarray(w, bool) for _, w in cols])

    def mk():
        fab = Fabric.build("multi_pod", forward_ns=10.0, rt_extra_ns=4.0,
                           num_pods=num_pods,
                           hosts_per_pod=num_hosts // num_pods)
        return [fab.mount(f"h{i}", f"d{i}", _mk_device("dram"))
                for i in range(num_hosts)]

    un = MultiHostReplay(mk(), outstanding=8, metrics=MetricsSpec())
    ru = un.run_arrays(addrs, writes)
    shd = ShardedMultiHostReplay(mk(), outstanding=8, metrics=MetricsSpec())
    rs = shd.run_arrays(addrs, writes)
    sh_exact = _multi_exact(ru, rs) and all(
        a.accesses == b.accesses and a.bytes_moved == b.bytes_moved
        for a, b in zip(ru.per_host, rs.per_host))
    metrics_equal = (ru.metrics.to_jsonable() == rs.metrics.to_jsonable())
    assert sh_exact and metrics_equal, \
        "sharded fleet replay diverged from the unsharded fused lane"
    out = {
        "hosts": num_hosts,
        "accesses_per_host": accesses,
        "n_accesses": num_hosts * accesses,
        "workload": {"kind": spec.kind, "num_pages": spec.num_pages,
                     "zipf_s": spec.zipf_s, "write_frac": spec.write_frac,
                     "seed": FLEET_SEED, "synthesis": "jnp (on device)"},
        "fabric": {"kind": "multi_pod", "num_pods": num_pods,
                   "hosts_per_pod": num_hosts // num_pods},
        "mesh": dict(shd.last_mesh),
        "tick_exact_sharded_vs_unsharded": bool(sh_exact),
        "metrics_equal_sharded_vs_unsharded": bool(metrics_equal),
        "elapsed_ticks": int(rs.elapsed_ticks),
        "sum_latency_ticks": int(sum(r.sum_latency_ticks
                                     for r in rs.per_host)),
        "p50_ticks": rs.metrics.percentile_ticks(50),
        "p99_ticks": rs.metrics.percentile_ticks(99),
    }
    if check_python:
        py = MultiHostDriver(mk(), outstanding=8).run(
            make_traces(spec, FLEET_SEED, num_hosts, accesses))
        py_exact = _multi_exact(py, ru)
        assert py_exact, "fused fleet replay diverged from the driver"
        out["tick_exact_vs_python"] = bool(py_exact)
    return out


#: the append-only single-lane re-record map: ``--lanes a,b`` refreshes
#: just these keys of an existing BENCH_replay.json, leaving every other
#: recorded number byte-for-byte untouched
LANE_COLLECTORS = {
    "faults": ("faults", collect_fault_derived),
    "availability": ("availability", collect_availability_derived),
    "fleet": ("fleet", collect_fleet_derived),
}


def merge_lanes(lanes) -> str:
    """Append/refresh ONLY the named derived lanes of an existing
    ``BENCH_replay.json`` — previously recorded wall-clock timings stay
    byte-for-byte untouched."""
    unknown = [x for x in lanes if x not in LANE_COLLECTORS]
    if unknown:
        raise SystemExit(f"unknown lane(s) {unknown}; "
                         f"choose from {sorted(LANE_COLLECTORS)}")
    with open(OUT_JSON) as f:
        report = json.load(f)
    for lane in lanes:
        key, fn = LANE_COLLECTORS[lane]
        report[key] = fn()
    with open(OUT_JSON, "w") as f:
        json.dump(report, f, indent=2)
    return os.path.abspath(OUT_JSON)


def merge_availability_lane() -> str:
    """Back-compat alias: ``merge_lanes(["availability"])``."""
    return merge_lanes(["availability"])


def bench_replay() -> List[Row]:
    trace = _trace(N)
    addrs = np.asarray([a for a, _, _ in trace], np.int64)
    writes = np.asarray([w for _, _, w in trace], bool)

    report = {
        "n_accesses": N,
        "config": {
            "cache_frames": CACHE_FRAMES,
            "footprint_pages": FOOTPRINT_PAGES,
            "write_frac": WRITE_FRAC,
            "outstanding": 32,
            "block_sizes": list(BLOCK_SIZES),
            "repeats": REPEATS,
        },
        "target_speedup": TARGETS,
        "devices": {},
    }
    rows: List[Row] = []
    for name in ("dram", "pmem", "cxl-ssd-cache"):
        lanes = report["devices"][name] = _bench_device(name, trace,
                                                        addrs, writes)
        py_s = lanes["python"]["seconds"]
        rows.append((f"replay/{name}/python", py_s * 1e6 / N,
                     f"{N / py_s / 1e3:.0f}kacc/s"))
        for lane, v in lanes.items():
            if lane == "python" or not isinstance(v, dict):
                continue
            s = v["steady_seconds"]
            tag = ("exact" if v.get("tick_exact_vs_python")
                   else "analytic")
            rows.append((f"replay/{name}/{lane}", s * 1e6 / N,
                         f"{v['speedup_vs_python']:.1f}x,{tag}"))

    report["multihost"] = {}
    for nh in MULTI_HOSTS:
        lane = report["multihost"][f"cxl-ssd-cache x{nh}"] = \
            _bench_multihost(nh)
        rows.append((f"replay/multihost/cxl-ssd-cache-x{nh}",
                     lane["steady_seconds"] * 1e6 / (nh * MULTI_N),
                     f"{lane['speedup_vs_python']:.1f}x,exact"))
    report["multihost_target_speedup"] = MULTI_TARGET
    report["multihost_meets_target"] = all(
        v["speedup_vs_python"] >= MULTI_TARGET
        for v in report["multihost"].values())

    report["streaming"] = _bench_streaming()
    for ch, v in report["streaming"]["chunks"].items():
        rows.append((f"replay/streaming/dram-chunk{ch}",
                     v["ns_per_access"] / 1e3,
                     f"{v['acc_per_sec'] / 1e3:.0f}kacc/s,"
                     f"{'exact' if v['tick_exact_vs_oneshot'] else 'DIVERGED'},"
                     f"{v['peak_input_bound_bytes'] >> 10}KiB-in"))

    report["faults"] = collect_fault_derived()
    for scen, v in report["faults"].items():
        if isinstance(v, dict):
            rows.append((f"replay/faults/{scen}", 0.0,
                         ("exact" if v["tick_exact_vs_python"]
                          else "DIVERGED")))

    report["availability"] = collect_availability_derived()
    for key, v in report["availability"].items():
        if isinstance(v, dict) and "tick_exact_vs_python" in v:
            rows.append((f"replay/availability/{key}", 0.0,
                         ("exact" if v["tick_exact_vs_python"]
                          else "DIVERGED")))

    fleet = report["fleet"] = collect_fleet_derived()
    rows.append((
        f"replay/fleet/multipod{fleet['fabric']['num_pods']}"
        f"-x{fleet['hosts']}", 0.0,
        f"{'exact' if fleet['tick_exact_sharded_vs_unsharded'] else 'DIVERGED'},"
        f"D{fleet['mesh']['device_count']}"))

    report["speedup_dram_best"] = report["devices"]["dram"][
        "best_exact_speedup"]
    report["speedup_pmem_best"] = report["devices"]["pmem"][
        "best_exact_speedup"]
    report["speedup_cxl_ssd_cache_best"] = report["devices"][
        "cxl-ssd-cache"]["best_exact_speedup"]
    report["meets_target"] = all(
        report["devices"][d]["meets_target"] for d in TARGETS) and \
        report["multihost_meets_target"]
    os.makedirs(os.path.dirname(os.path.abspath(OUT_JSON)), exist_ok=True)
    with open(OUT_JSON, "w") as f:
        json.dump(report, f, indent=2)
    rows.append(("replay/meets_target", 0.0,
                 f"{report['meets_target']}"
                 f"(dram{report['speedup_dram_best']:.0f}x,"
                 f"pmem{report['speedup_pmem_best']:.0f}x,"
                 f"ssd{report['speedup_cxl_ssd_cache_best']:.0f}x)"))
    return rows


ALL = [bench_replay]


if __name__ == "__main__":
    import sys

    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    if "--availability-only" in sys.argv:
        # refresh just the derived availability lane, leaving every
        # previously recorded timing untouched
        print(f"# wrote availability lane -> {merge_availability_lane()}")
        sys.exit(0)
    if "--lanes" in sys.argv:
        # re-record only the named derived lanes (e.g. --lanes fleet):
        # append-only merge into the existing artifact
        names = sys.argv[sys.argv.index("--lanes") + 1].split(",")
        print(f"# wrote lane(s) {names} -> {merge_lanes(names)}")
        sys.exit(0)
    print("name,us_per_call,derived")
    for fn in ALL:
        for name, us_per_call, derived in fn():
            print(f"{name},{us_per_call:.2f},{derived}")
    print(f"# wrote {os.path.abspath(OUT_JSON)}")
