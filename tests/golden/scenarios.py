"""Golden-trace scenario table + engine runners.

One scenario = one (device stack, attach mode, seeded trace) combination.
The fixture (``golden_traces.json``) pins per-access latencies so that
*silent* divergence — python and scan drifting together, or an engine's
latency model changing without anyone noticing — fails loudly, which the
pairwise python==scan property tests cannot catch.

Contracts pinned per scenario:

* ``python_scan`` — per-access latency ticks that the interpreted
  ``TraceDriver``/``MultiHostDriver`` path, the fused lax.scan replay, the
  **blocked** scan (``block_size=BLOCK_SIZE``), and the **associative**
  log-depth lane (where it certifies the stack — stateless DRAM/PMEM
  media) must ALL reproduce exactly.  One pin, every tick-exact lane.
* ``pallas`` — the Pallas engine's own per-access latencies where the
  engine supports the stack (cached CXL-SSD).  Its analytic latency model
  is *not* tick-identical to python; pinning its output separately catches
  silent regressions in that model too.  The golden runner passes
  ``validate=True`` so every conformance pass also cross-checks the
  in-kernel latency chain against the shared associative reconstruction.

The ``@stream`` scenarios replay with ``outstanding=32`` — the
bandwidth-bound regime the associative lane is built for (it converges in
a couple of sweeps there, vs. crawling through the LFB feedback on the
``outstanding=8`` scenarios).

Regenerate with ``PYTHONPATH=src python tests/golden/regen.py`` after an
intentional timing-model change, and say so in the commit message.  Regen
refuses to alter any previously pinned scenario — history can only be
extended, never silently rewritten.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

FIXTURE = Path(__file__).with_name("golden_traces.json")

CACHE_KW = dict(capacity_bytes=16 * 4096, mshr_entries=4, writeback_buffer=2)
DEVICES = ["dram", "cxl-dram", "pmem", "cxl-ssd", "cxl-ssd-cache"]
N_ACCESSES = 160
OUTSTANDING = 8
STREAM_OUTSTANDING = 32      # @stream scenarios: bandwidth-bound issue depth
BLOCK_SIZE = 8               # blocked-scan lane pinned alongside B=1
ASSOC_SWEEPS = 256           # short traces afford a generous Kleene budget

# multi-host tentpole scenario: QoS weights + ECMP on a spine-leaf pool
MULTI = dict(num_hosts=3, num_leaves=2, num_spines=2,
             qos_weights={"h0": 3.0, "h1": 1.0, "h2": 1.0})

# stacked-state scenarios: multi-host cached CXL-SSD (PR 5 tentpole) —
# private mounts, a shared pool, per-host caches over one shared flash
# (GC-triggering), and a single-host GC-pressure trace
MULTI_SSD_HOSTS = {"multihost-ssd-mounts": 2, "multihost-ssd-pool": 4,
                   "multihost-ssd-sharedflash": 2}

# fault-injection scenarios (PR 7): deterministic FaultPlans pinned
# end-to-end — link CRC-retry bursts under ECMP, a port-down window that
# forces failover reroutes, and NAND read-retry + erase-fail retirement
# (+ read poison) on a GC-pressured cached SSD
FAULT_SCENARIOS = ("faults-linkretry@spine_leaf",
                   "faults-portdown-failover@mesh",
                   "faults-nand-retry@direct")

# multi-host transport-fault scenarios (PR 9): the fused multi-host lanes
# mirror fabric fault plans on per-host mounts; the pins carry each
# host's per-access latencies AND the aggregated fault counters
# (degraded accesses, ECMP failovers, link retries)
MULTI_FAULT_HOSTS = {"faults-portdown@multihost_x2": 2,
                     "faults-linkretry@spine_leaf_x4": 4}

# rack-scale fleet scenario (PR 10): a synthesized Zipfian fleet on a
# 2-pod datacenter fabric (cross-pod host->device paths through the core
# tier), replayed by the SHARDED shard_map lane — the pin holds the
# interpreted MultiHostDriver's latencies, so golden conformance certifies
# sharded == python tick-for-tick at whatever device count the run forces
# (D=1 in the default tier; the CI fleet-smoke job re-runs it on 8 forced
# host-platform devices)
FLEET_SCENARIO = "fleet-zipf@multipod_2x4"
FLEET_GOLDEN_HOSTS = 8


def scenario_names():
    names = [f"{d}@{attach}" for d in DEVICES
             for attach in ("direct", "fabric")]
    names.append("multihost-qos-ecmp")
    names += ["dram@stream", "pmem@stream"]
    names += sorted(MULTI_SSD_HOSTS)
    names.append("ssd-gc@direct")
    names += list(FAULT_SCENARIOS)
    # single-host fabric port with weighted (QoS) arbitration: pins the
    # qos_throttle_events counter python==fused (PR 8 — previously the
    # fused single-host lanes hardcoded 0 and the divergence was
    # deliberately left unpinned)
    names.append("dram-qos@fabric")
    names += sorted(MULTI_FAULT_HOSTS)
    names.append(FLEET_SCENARIO)
    return names


def is_multi(name: str) -> bool:
    """Multi-host scenarios pin one latency list per host."""
    return (name.startswith("multihost") or name in MULTI_FAULT_HOSTS
            or name.startswith("fleet"))


def scenario_outstanding(name: str) -> int:
    return STREAM_OUTSTANDING if name.endswith("@stream") else OUTSTANDING


def make_trace(seed: int, n: int = N_ACCESSES, pages: int = 24,
               write_frac: float = 0.3):
    rng = np.random.default_rng(seed)
    addrs = rng.integers(0, pages, n) * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < write_frac
    return [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]


def _mk_device(name: str):
    from repro.core.cache.dram_cache import DRAMCacheConfig
    from repro.core.devices import make_device

    if name == "cxl-ssd-cache":
        return make_device(name, cache_cfg=DRAMCacheConfig(policy="lru",
                                                           **CACHE_KW))
    return make_device(name)


def _gc_ssd_cfg(cap_pages: int):
    """Tiny flash geometry so short pinned traces reach the GC watermark."""
    from repro.core.ssd.hil import SSDConfig
    from repro.core.ssd.pal import NANDTiming

    return SSDConfig(capacity_bytes=cap_pages * 4096, page_bytes=4096,
                     channels=2, dies_per_channel=2, pages_per_block=8,
                     timing=NANDTiming.low_latency(), hil_overhead_ns=1000.0)


def _make_fault_target(name: str):
    """Fresh target with its scenario's deterministic FaultPlan installed
    (the plan is a pure function of (seed, config): rebuilding the target
    reproduces the exact same fault schedule)."""
    from repro.core.cache.dram_cache import DRAMCacheConfig
    from repro.core.devices import make_device
    from repro.core.fabric import Fabric
    from repro.core.faults import FaultConfig, FaultPlan, install

    if name == "faults-linkretry@spine_leaf":
        fab = Fabric.build("spine_leaf", num_hosts=2, num_devices=2,
                           num_leaves=2, num_spines=2, ecmp=True)
        tgt = fab.mount("h0", "d0", _mk_device("dram"))
        install(FaultPlan(FaultConfig(link_retry_rate=0.25), seed=7), [tgt])
        return tgt
    if name == "faults-portdown-failover@mesh":
        fab = Fabric.build("mesh", num_hosts=2, num_devices=2)
        tgt = fab.mount("h0", "d0", _mk_device("cxl-dram"))
        install(FaultPlan(FaultConfig(
            down_links=(("s0_0", "s0_1", 10, 70),)), seed=7), [tgt])
        return tgt
    # faults-nand-retry@direct: GC-pressured cached SSD so the pinned
    # trace also exercises erase-fail block retirement and read poison
    dev = make_device("cxl-ssd-cache", ssd_cfg=_gc_ssd_cfg(750),
                      cache_cfg=DRAMCacheConfig(
                          capacity_bytes=8 * 4096, mshr_entries=4,
                          writeback_buffer=2))
    install(FaultPlan(FaultConfig(nand_read_retry_rate=0.3,
                                  erase_fail_rate=0.5,
                                  poison_rate=0.1), seed=0), [dev])
    return dev


def make_target(name: str):
    """Fresh device for ``<device>@<attach>`` scenarios (``@stream`` is
    directly attached, replayed at the streaming issue depth;
    ``ssd-gc`` is a cached CXL-SSD with a near-full tiny flash; the
    ``faults-*`` scenarios carry an installed deterministic fault plan)."""
    from repro.core.cache.dram_cache import DRAMCacheConfig
    from repro.core.devices import make_device
    from repro.core.fabric import Fabric

    if name in FAULT_SCENARIOS:
        return _make_fault_target(name)
    if name == "dram-qos@fabric":
        # weighted-arbitration fabric port: the single-host QoS virtual
        # clock can outrun arrivals, so the throttle counter moves
        fab = Fabric.build("two_level", num_hosts=2, num_devices=2,
                           num_leaves=2, qos_weights={"h0": 3.0, "h1": 1.0})
        return fab.mount("h1", "d1", _mk_device("dram"))
    device, attach = name.split("@")
    if device == "ssd-gc":
        return make_device("cxl-ssd-cache", ssd_cfg=_gc_ssd_cfg(750),
                           cache_cfg=DRAMCacheConfig(
                               capacity_bytes=8 * 4096, mshr_entries=4,
                               writeback_buffer=2))
    dev = _mk_device(device)
    if attach == "fabric":
        fab = Fabric.build("two_level", num_hosts=2, num_devices=2,
                           num_leaves=2)
        return fab.mount("h1", "d1", dev)
    return dev


def _make_multi_fault_targets(name: str):
    """Per-host fabric mounts on one spine-leaf with a deterministic
    transport FaultPlan installed — a down window that forces ECMP
    failover (x2) or CRC link-retry bursts (x4)."""
    from repro.core.devices import make_device
    from repro.core.fabric import Fabric
    from repro.core.faults import FaultConfig, FaultPlan, install

    nh = MULTI_FAULT_HOSTS[name]
    fab = Fabric.build("spine_leaf", num_hosts=nh, num_devices=nh,
                       num_leaves=2, num_spines=2, ecmp=True)
    tgts = [fab.mount(f"h{i}", f"d{i}", make_device("dram"))
            for i in range(nh)]
    if name == "faults-portdown@multihost_x2":
        cfg = FaultConfig(down_links=(("s0", "sp0", 20, 90),))
    else:
        cfg = FaultConfig(link_retry_rate=0.2, link_retry_max=2)
    install(FaultPlan(cfg, seed=11), tgts)
    return tgts


def make_multi_targets(name: str = "multihost-qos-ecmp"):
    """Fresh targets + traces builder inputs for the multi-host scenarios."""
    from repro.core.cache.dram_cache import DRAMCacheConfig
    from repro.core.devices import CachedCXLSSDDevice, DRAMDevice
    from repro.core.fabric import Fabric, MemoryPool
    from repro.core.ssd.hil import HIL

    if name in MULTI_FAULT_HOSTS:
        return _make_multi_fault_targets(name)
    if name == FLEET_SCENARIO:
        from repro.core.devices import make_device

        fab = Fabric.build("multi_pod", ecmp=True, num_pods=2,
                           hosts_per_pod=FLEET_GOLDEN_HOSTS // 2)
        return [fab.mount(f"h{i}", f"d{i}", make_device("dram"))
                for i in range(FLEET_GOLDEN_HOSTS)]
    if name == "multihost-qos-ecmp":
        fab = Fabric.build("spine_leaf", num_hosts=MULTI["num_hosts"],
                           num_devices=2, num_leaves=MULTI["num_leaves"],
                           num_spines=MULTI["num_spines"], ecmp=True,
                           qos_weights=MULTI["qos_weights"])
        pool = MemoryPool(fab, {"d0": DRAMDevice(), "d1": DRAMDevice()})
        return pool.views([f"h{i}" for i in range(MULTI["num_hosts"])])
    cache_cfg = dict(policy="lru", **CACHE_KW)
    if name == "multihost-ssd-pool":
        fab = Fabric.build("two_level", num_hosts=4, num_devices=2,
                           num_leaves=2)
        pool = MemoryPool(fab, {
            "d0": CachedCXLSSDDevice(
                cache_cfg=DRAMCacheConfig(**cache_cfg)),
            "d1": CachedCXLSSDDevice(
                cache_cfg=DRAMCacheConfig(**cache_cfg))})
        return pool.views([f"h{i}" for i in range(4)])
    nh = MULTI_SSD_HOSTS[name]
    fab = Fabric.build("two_level", num_hosts=nh, num_devices=nh,
                       num_leaves=2)
    hil = (HIL(_gc_ssd_cfg(48))
           if name == "multihost-ssd-sharedflash" else None)
    return [fab.mount(f"h{i}", f"d{i}", CachedCXLSSDDevice(
                cache_cfg=DRAMCacheConfig(**cache_cfg), hil=hil))
            for i in range(nh)]


def multi_traces(name: str = "multihost-qos-ecmp"):
    if name in MULTI_FAULT_HOSTS:
        return [make_trace(400 + h) for h in range(MULTI_FAULT_HOSTS[name])]
    if name == FLEET_SCENARIO:
        # synthesized (hash-seeded) Zipfian fleet traffic — the workload
        # generator twins, pinned end-to-end through the replay engines
        from repro.data import WorkloadSpec, make_traces

        spec = WorkloadSpec("zipfian", num_pages=48, zipf_s=1.1)
        return make_traces(spec, 29, FLEET_GOLDEN_HOSTS, N_ACCESSES)
    if name == "multihost-ssd-sharedflash":
        # write-heavy churn past the 16-page cache: reaches the tiny shared
        # flash's GC watermark (sustained, clean-victim collections)
        return [make_trace(300 + h, n=N_ACCESSES, pages=24, write_frac=0.7)
                for h in range(MULTI_SSD_HOSTS[name])]
    nh = MULTI_SSD_HOSTS.get(name, MULTI["num_hosts"])
    return [make_trace(100 + h) for h in range(nh)]


class ServiceTap:
    """Wrap a MemDevice, recording the latency of every service call —
    the interpreted drivers' per-access latencies, without touching them."""

    def __init__(self, dev):
        self._dev = dev
        self.latencies = []

    def __getattr__(self, name):
        return getattr(self._dev, name)

    def service(self, now, addr, size, write, posted=False):
        done = self._dev.service(now, addr, size, write, posted)
        self.latencies.append(int(done - now))
        return done


def _summ(latencies, result):
    return {
        "latency_ticks": [int(x) for x in latencies],
        "elapsed_ticks": int(result.elapsed_ticks),
        "sum_latency_ticks": int(result.sum_latency_ticks),
        "end_tick": int(result.end_tick),
    }


def scenario_trace(name: str):
    """The pinned trace for a single-host scenario (seeded random; the GC
    scenario uses the deterministic near-full fill + scattered rewrites so
    victim blocks carry valid pages and the migration path is pinned)."""
    if name == "ssd-gc@direct":
        trace = [(p * 4096, 64, True) for p in range(750)]
        trace += [(((k * 9) % 750) * 4096 + (k % 64) * 64, 64, True)
                  for k in range(40)]
        return trace
    if name == "faults-nand-retry@direct":
        # near-full fill + scattered rewrites (GC + erase-fail retirement)
        # + a read tail (NAND read retries through cache misses, and read
        # ordinals the poison schedule can flag)
        trace = [(p * 4096, 64, True) for p in range(750)]
        trace += [(((k * 9) % 750) * 4096 + (k % 64) * 64, 64, True)
                  for k in range(40)]
        trace += [(((k * 131) % 750) * 4096, 64, False) for k in range(24)]
        return trace
    return make_trace(hash_seed(name))


def run_python(name: str):
    """Interpreted reference: per-access latencies + scalar summary."""
    from repro.core.workloads.driver import MultiHostDriver, TraceDriver

    if is_multi(name):
        taps = [ServiceTap(t) for t in make_multi_targets(name)]
        res = MultiHostDriver(taps, outstanding=OUTSTANDING).run(
            multi_traces(name))
        return [_summ(tap.latencies, host)
                for tap, host in zip(taps, res.per_host)]
    tap = ServiceTap(make_target(name))
    res = TraceDriver(tap, outstanding=scenario_outstanding(name)).run(
        scenario_trace(name))
    return _summ(tap.latencies, res)


def run_scan(name: str, block_size: int = 1):
    """Fused lax.scan replay (optionally blocked): per-access latencies +
    scalar summary.  Any ``block_size`` must match the ``python_scan``
    pins exactly.  ``fleet-*`` scenarios replay through the SHARDED
    shard_map lane, so the pins certify it at the run's device count."""
    from repro.core.replay import (MultiHostReplay, ReplayEngine,
                                   ShardedMultiHostReplay)

    if is_multi(name):
        cls = (ShardedMultiHostReplay if name.startswith("fleet")
               else MultiHostReplay)
        eng = cls(make_multi_targets(name),
                  outstanding=OUTSTANDING,
                  block_size=block_size)
        res, lat = eng.run_recorded(multi_traces(name))
        return [_summ(l.tolist(), host)
                for l, host in zip(lat, res.per_host)]
    res = ReplayEngine(make_target(name),
                       outstanding=scenario_outstanding(name),
                       block_size=block_size).run(scenario_trace(name))
    return _summ(res.latency_ticks.tolist(), res)


def run_python_metrics(name: str):
    """Interpreted reference metrics bundle (JSON form): the schema every
    fused lane must reproduce value-for-value."""
    from repro.core.replay.metrics import MetricsSpec
    from repro.core.workloads.driver import MultiHostDriver, TraceDriver

    spec = MetricsSpec()
    if is_multi(name):
        res = MultiHostDriver(make_multi_targets(name),
                              outstanding=OUTSTANDING,
                              metrics=spec).run(multi_traces(name))
    else:
        res = TraceDriver(make_target(name),
                          outstanding=scenario_outstanding(name),
                          engine="python",
                          metrics=spec).run(scenario_trace(name))
    return res.metrics.to_jsonable()


def run_scan_metrics(name: str):
    """Fused-lane metrics bundle (JSON form): it must match the
    interpreted stats dicts exactly (``fleet-*`` through the sharded
    lane)."""
    from repro.core.replay import (MultiHostReplay, ReplayEngine,
                                   ShardedMultiHostReplay)
    from repro.core.replay.metrics import MetricsSpec

    spec = MetricsSpec()
    if is_multi(name):
        cls = (ShardedMultiHostReplay if name.startswith("fleet")
               else MultiHostReplay)
        res = cls(make_multi_targets(name),
                  outstanding=OUTSTANDING,
                  metrics=spec).run(multi_traces(name))
    else:
        res = ReplayEngine(make_target(name),
                           outstanding=scenario_outstanding(name),
                           metrics=spec).run(scenario_trace(name))
    return res.metrics.to_jsonable()


def run_scan_blocked(name: str):
    """Blocked-scan lane (``block_size=BLOCK_SIZE``): must match the
    ``python_scan`` pins — block seams are tick-invisible."""
    return run_scan(name, block_size=BLOCK_SIZE)


def run_assoc(name: str):
    """Log-depth associative lane: must match the ``python_scan`` pins on
    every stack it certifies (stateless DRAM/PMEM media)."""
    from repro.core.replay import AssocReplayEngine

    res = AssocReplayEngine(make_target(name),
                            outstanding=scenario_outstanding(name),
                            max_sweeps=ASSOC_SWEEPS).run(
        scenario_trace(name))
    return _summ(res.latency_ticks.tolist(), res)


def assoc_supported(name: str) -> bool:
    return name.split("@")[0] in ("dram", "cxl-dram", "pmem") \
        and not is_multi(name)


def run_pallas(name: str):
    """Pallas engine (cached CXL-SSD only): its own pinned latencies, with
    the associative latency reconstruction cross-check enabled."""
    from repro.core.replay.pallas_engine import run_pallas as _run
    from repro.core.replay.spec import trace_to_arrays

    addrs, writes, size = trace_to_arrays(scenario_trace(name))
    res = _run(make_target(name), addrs, writes, size=size,
               outstanding=scenario_outstanding(name), validate=True)
    return _summ(res.latency_ticks.tolist(), res)


def pallas_supported(name: str) -> bool:
    return name.startswith("cxl-ssd-cache@")


def hash_seed(name: str) -> int:
    """Stable small per-scenario trace seed (NOT Python's randomized
    ``hash``)."""
    return sum(ord(c) for c in name) % 997


def load_fixture() -> dict:
    with open(FIXTURE) as fh:
        return json.load(fh)
