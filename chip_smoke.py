#!/usr/bin/env python3
"""Chip smoke test: the fused replay path on a TPU at the paper's device size.

    python chip_smoke.py [--seed N]               # one chip, phases 1-6
    python chip_smoke.py --chips 4 [--seed N]     # sharded fleet phase only

One process, no subprocesses.  It refuses to run unless JAX's first device
is a TPU: there is no CPU fallback.  Every input is synthesized from
``--seed`` by :mod:`repro.data.workloads`.  The device is the paper's
Table I cached CXL-SSD (``make_device("cxl-ssd-cache")``: a 16 MiB LRU
DRAM cache, 4,096 frames, over 16 GiB of flash), and the trace is 2^20
64-byte accesses, zipfian (s=0.99) over 65,536 pages (256 MiB, 16x the
cache), 30% writes.

Each phase runs its entry point once and prints one line: its name, the
call's wall time split into ``compile_s`` (XLA lowering and compilation,
from JAX's compile-duration events) and ``run_s`` (the rest, ending in
results on the host), and its check.  These timings are smoke output, not
benchmark results.  Any failed check exits non-zero before the
last line, which is the only JSON line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_ACCESSES = 1 << 20
FOOTPRINT_PAGES = 65_536
ZIPF_S = 0.99
WRITE_FRAC = 0.3
PY_PREFIX = 20_000          # single-host accesses checked against python
STREAM_CHUNK = 65_536
MULTI_HOSTS = 4
MULTI_N = 65_536            # accesses per host
MULTI_PREFIX = 5_000        # per-host accesses checked against python
ASSOC_N = 200_000           # README's bandwidth-bound DRAM trace
FLEET_HOSTS = 64
# accesses per host: 2^18 steps in all.  A fused multi-host step costs
# ~0.1 ms on a v5e, and the phase replays the fleet twice (sharded on four
# chips, unsharded on one), so this keeps the four-chip call to minutes.
FLEET_N = 4_096


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent lowering and compiling, summed from its
    compile-duration events while registered."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.seconds += duration

    def timed(self, fn):
        """Call ``fn`` once: (compile seconds, other seconds, result)."""
        c0 = self.seconds
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        compile_s = self.seconds - c0
        return compile_s, wall - compile_s, out


def report(name: str, compile_s: float, run_s: float, checks: str) -> None:
    print(f"phase {name}: compile_s={compile_s:.3f} run_s={run_s:.3f} "
          f"check=passed ({checks})", flush=True)


class ServiceTap:
    """Wraps a device and records each access's latency as the
    interpreted driver sees it (``done - now`` of every service call)."""

    def __init__(self, dev):
        self._dev = dev
        self.latencies = []

    def __getattr__(self, name):
        return getattr(self._dev, name)

    def service(self, now, addr, size, write, posted=False):
        done = self._dev.service(now, addr, size, write, posted)
        self.latencies.append(int(done - now))
        return done


def summary(r):
    return (r.accesses, r.bytes_moved, r.elapsed_ticks, r.sum_latency_ticks,
            r.end_tick)


def tuples(addrs, writes):
    return [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]


def workload():
    from repro.data import WorkloadSpec

    return WorkloadSpec("zipfian", num_pages=FOOTPRINT_PAGES, zipf_s=ZIPF_S,
                        write_frac=WRITE_FRAC)


# ------------------------------------------------------------- phases
def phase_scan(clock, addrs, writes):
    """1. Single-host scan at full size, checked on a prefix against the
    interpreted driver (latencies, summary and metrics bundle)."""
    import numpy as np

    from repro.core.devices import make_device
    from repro.core.replay import MetricsSpec, ReplayEngine
    from repro.core.workloads.driver import TraceDriver

    def run(a=addrs, w=writes):
        return ReplayEngine(make_device("cxl-ssd-cache"),
                            metrics=MetricsSpec()).run_arrays(a, w)

    compile_s, run_s, res = clock.timed(run)
    check(res.accesses == addrs.size
          and np.all(res.latency_ticks > 0), "scan: latencies not positive")

    pa, pw = addrs[:PY_PREFIX], writes[:PY_PREFIX]
    tap = ServiceTap(make_device("cxl-ssd-cache"))
    py = TraceDriver(tap, engine="python").run(tuples(pa, pw))
    py_m = TraceDriver(make_device("cxl-ssd-cache"), engine="python",
                       metrics=MetricsSpec()).run(tuples(pa, pw))
    pre = run(pa, pw)
    py_lat = np.asarray(tap.latencies, np.int64)
    check(np.array_equal(pre.latency_ticks, py_lat),
          "scan prefix latencies differ from python")
    check(np.array_equal(res.latency_ticks[:PY_PREFIX], py_lat),
          "full-size scan latencies differ from python on the prefix")
    check(summary(pre) == summary(py), "scan prefix summary differs")
    check(pre.metrics.to_jsonable() == py_m.metrics.to_jsonable(),
          "scan prefix metrics bundle differs from python")
    report("1-scan", compile_s, run_s,
           f"{PY_PREFIX} accesses tick-identical to python, metrics equal; "
           f"hit_rate={res.metrics.hit_rate:.4f} "
           f"avg_latency_ns={res.avg_latency_ns:.1f}")
    return res


def phase_blocked(clock, addrs, writes, base):
    """2. Blocked scan (8 accesses per step), tick-identical to phase 1."""
    import numpy as np

    from repro.core.devices import make_device
    from repro.core.replay import MetricsSpec, ReplayEngine

    compile_s, run_s, res = clock.timed(lambda: ReplayEngine(
        make_device("cxl-ssd-cache"), block_size=8,
        metrics=MetricsSpec()).run_arrays(addrs, writes))
    check(np.array_equal(res.latency_ticks, base.latency_ticks),
          "blocked scan latencies differ from the scan")
    check(summary(res) == summary(base), "blocked scan summary differs")
    check(res.metrics.to_jsonable() == base.metrics.to_jsonable(),
          "blocked scan metrics bundle differs")
    report("2-blocked", compile_s, run_s,
           f"{addrs.size} accesses tick-identical to phase 1")


def phase_stream(clock, addrs, writes, base):
    """3. Streaming replay from an on-disk TraceStore: metrics only."""
    from repro.core.devices import make_device
    from repro.core.replay import MetricsSpec, replay_stream
    from repro.data.trace_store import TraceStore

    with tempfile.TemporaryDirectory() as tmp:
        store = TraceStore.write(Path(tmp) / "trace", addrs, writes)
        compile_s, run_s, res = clock.timed(lambda: replay_stream(
            store, make_device("cxl-ssd-cache"), chunk_size=STREAM_CHUNK,
            return_latencies=False, metrics=MetricsSpec()))
    check(res.metrics.to_jsonable() == base.metrics.to_jsonable(),
          "streamed metrics bundle differs from phase 1")
    check(summary(res) == summary(base), "streamed summary differs")
    report("3-stream", compile_s, run_s,
           f"{addrs.size // STREAM_CHUNK} chunks, metrics equal to phase 1")


def phase_multihost(clock, seed):
    """4. Multi-host scan: cached CXL-SSD mounts on a two-level fabric."""
    import numpy as np

    from repro.core.devices import make_device
    from repro.core.fabric import Fabric
    from repro.core.replay import MultiHostReplay
    from repro.core.workloads.driver import MultiHostDriver
    from repro.data import make_traces

    def mounts():
        fab = Fabric.build("two_level", num_hosts=MULTI_HOSTS,
                           num_devices=MULTI_HOSTS, num_leaves=2)
        return [fab.mount(f"h{i}", f"d{i}", make_device("cxl-ssd-cache"))
                for i in range(MULTI_HOSTS)]

    traces = make_traces(workload(), seed, MULTI_HOSTS, MULTI_N)
    compile_s, run_s, res = clock.timed(
        lambda: MultiHostDriver(mounts(), engine="scan").run(traces))
    check(all(r.accesses == MULTI_N for r in res.per_host),
          "multi-host scan lost accesses")

    prefix = [t[:MULTI_PREFIX] for t in traces]
    taps = [ServiceTap(m) for m in mounts()]
    py = MultiHostDriver(taps, engine="python").run(prefix)
    rp, lat = MultiHostReplay(mounts()).run_recorded(prefix)
    for h, tap in enumerate(taps):
        check(np.array_equal(lat[h], np.asarray(tap.latencies, np.int64)),
              f"multi-host host {h} latencies differ from python")
        check(summary(rp.per_host[h]) == summary(py.per_host[h]),
              f"multi-host host {h} summary differs from python")
    check(rp.elapsed_ticks == py.elapsed_ticks, "multi-host elapsed differs")
    report("4-multihost", compile_s, run_s,
           f"{MULTI_HOSTS}x{MULTI_N}; {MULTI_HOSTS}x{MULTI_PREFIX} prefix "
           "tick-identical to python")


def phase_pallas(clock, addrs, writes):
    """5. The fused Pallas cache kernel, compiled; decisions checked
    against the lax.scan cache oracle."""
    import numpy as np

    from repro.core.cache.trace_sim import TraceCacheSim
    from repro.core.devices import make_device
    from repro.core.replay.pallas_engine import pallas_params, run_pallas
    from repro.kernels.ops import interpret_mode

    compiled = not interpret_mode()
    check(compiled, "pallas kernel would run in interpret mode")
    compile_s, run_s, res = clock.timed(lambda: run_pallas(
        make_device("cxl-ssd-cache"), addrs, writes, validate=True))
    kw = pallas_params(make_device("cxl-ssd-cache"), 0.5)
    hits, evicts, _ = TraceCacheSim(num_sets=kw["num_sets"], ways=kw["ways"],
                                    policy=kw["policy"]).run(
        addrs // 4096, writes)
    check(np.array_equal(res.hit_flags, np.asarray(hits)),
          "pallas hit flags differ from the cache oracle")
    check(np.array_equal(res.evict_flags, np.asarray(evicts)),
          "pallas dirty-evict flags differ from the cache oracle")
    report("5-pallas", compile_s, run_s,
           f"compiled={compiled} ways={kw['ways']} sets={kw['num_sets']}; "
           "hit/evict flags equal to the oracle, latency equal to the "
           f"associative reconstruction; hit_rate={res.hit_flags.mean():.4f}")


def phase_assoc(clock):
    """6. The associative lane on DRAM, bandwidth-bound trace."""
    import numpy as np

    from repro.core.devices import make_device
    from repro.core.replay import AssocReplayEngine, ReplayEngine

    i = np.arange(ASSOC_N, dtype=np.int64)
    addrs, writes = i * 64, i % 4 == 0
    compile_s, run_s, res = clock.timed(lambda: AssocReplayEngine(
        make_device("dram")).run_arrays(addrs, writes))
    ref = ReplayEngine(make_device("dram")).run_arrays(addrs, writes)
    check(np.array_equal(res.latency_ticks, ref.latency_ticks),
          "assoc latencies differ from the scan")
    check(summary(res) == summary(ref), "assoc summary differs from scan")
    report("6-assoc", compile_s, run_s,
           f"{ASSOC_N} accesses certified, tick-identical to the scan")


def phase_fleet(clock, seed, devices):
    """Four chips: the sharded fleet lane against the unsharded lane on
    one chip."""
    import numpy as np

    from repro.core.devices import make_device
    from repro.core.fabric import Fabric
    from repro.core.replay import (MetricsSpec, MultiHostReplay,
                                   ShardedMultiHostReplay)
    from repro.data import make_traces

    def mounts():
        fab = Fabric.build("multi_pod", ecmp=True, num_pods=2,
                           hosts_per_pod=FLEET_HOSTS // 2)
        return [fab.mount(f"h{i}", f"d{i}", make_device("cxl-ssd-cache"))
                for i in range(FLEET_HOSTS)]

    traces = make_traces(workload(), seed, FLEET_HOSTS, FLEET_N)
    eng = ShardedMultiHostReplay(mounts(), metrics=MetricsSpec(),
                                 devices=devices)
    sh_compile, sh_run, (rs, lat_s) = clock.timed(
        lambda: eng.run_recorded(traces))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    print(f"phase fleet-4chip sharded: compile_s={sh_compile:.3f} "
          f"run_s={sh_run:.3f} device_count={eng.last_mesh['device_count']} "
          f"peak_bytes_in_use={peaks}", flush=True)
    un_compile, un_run, (ru, lat_u) = clock.timed(
        lambda: MultiHostReplay(mounts(), metrics=MetricsSpec()
                                ).run_recorded(traces))
    check(eng.last_mesh["device_count"] == len(devices),
          f"mesh used {eng.last_mesh['device_count']} devices")
    for h in range(FLEET_HOSTS):
        check(np.array_equal(lat_s[h], lat_u[h]),
              f"fleet host {h} latencies: sharded != unsharded")
        check(summary(rs.per_host[h]) == summary(ru.per_host[h]),
              f"fleet host {h} summary: sharded != unsharded")
    check(rs.metrics.to_jsonable() == ru.metrics.to_jsonable(),
          "fleet metrics bundle: sharded != unsharded")
    report("fleet-4chip unsharded", un_compile, un_run,
           f"{FLEET_HOSTS}x{FLEET_N}: latencies and metrics bundle "
           "sharded == unsharded")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.compile_cache import use_persistent_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 2
    cache = use_persistent_cache()
    print(f"chip_smoke: {dev.device_kind} x{len(jax.devices())} "
          f"seed={args.seed} compile_cache={cache}", flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        if args.chips == 4:
            phase_fleet(clock, args.seed, jax.devices()[:4])
        else:
            from repro.data import host_trace_np

            addrs, writes = host_trace_np(workload(), args.seed, 0,
                                          N_ACCESSES)
            base = phase_scan(clock, addrs, writes)
            phase_blocked(clock, addrs, writes, base)
            phase_stream(clock, addrs, writes, base)
            phase_multihost(clock, args.seed)
            phase_pallas(clock, addrs, writes)
            phase_assoc(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
