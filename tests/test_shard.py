"""Rack-scale sharded fleet replay (repro.core.replay.shard): the
shard_map lane must be tick-identical — per-access latency streams,
MetricsBundle, fault counters — to the unsharded fused MultiHostReplay
(and hence to the interpreted MultiHostDriver) at H in {2, 8, 32} on a
multi-pod fabric, and must refuse the shapes it cannot shard (pooled
views, shared-flash HILs, chunked streaming) naming the covering lane.

The default tier runs on however many JAX devices the process has
(usually 1 — the same SPMD program on a single shard); the CI
``fleet-smoke`` job re-runs this file with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the
collectives cross real shard boundaries."""

import jax
import numpy as np
import pytest

from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.devices import make_device
from repro.core.fabric import Fabric
from repro.core.fabric.topology import build_topology
from repro.core.replay import (
    MetricsSpec,
    MultiHostReplay,
    ReplayUnsupported,
    ShardedMultiHostReplay,
    shard_count,
)
from repro.core.workloads.driver import MultiHostDriver
from repro.data import WorkloadSpec, make_traces, traces_np

CACHE_KW = dict(capacity_bytes=16 * 4096, mshr_entries=4, writeback_buffer=2)
N = 120
OUTSTANDING = 8


def _mk_dev(name="dram"):
    if name == "cxl-ssd-cache":
        return make_device(name, cache_cfg=DRAMCacheConfig(policy="lru",
                                                           **CACHE_KW))
    return make_device(name)


def _mounts(nh, name="dram", *, num_pods=2, ecmp=True, qos=False):
    qw = {f"h{i}": 1.0 + (i % 3) for i in range(nh)} if qos else None
    fab = Fabric.build("multi_pod", ecmp=ecmp, qos_weights=qw,
                       num_pods=num_pods, hosts_per_pod=nh // num_pods)
    return [fab.mount(f"h{i}", f"d{i}", _mk_dev(name)) for i in range(nh)]


def _traces(nh, n=N, kind="hotspot", seed=7):
    spec = WorkloadSpec(kind, num_pages=96, hot_frac=0.8, hot_pages=12,
                        zipf_s=1.1)
    return make_traces(spec, seed, nh, n)


def _tup(r):
    return (r.accesses, r.bytes_moved, r.elapsed_ticks,
            r.sum_latency_ticks, r.end_tick)


def _assert_identical(py, ru, lat_u, rs, lat_s):
    """python == unsharded == sharded: per-host aggregates and the full
    per-access latency streams."""
    assert py.elapsed_ticks == ru.elapsed_ticks == rs.elapsed_ticks
    for a, b, c in zip(py.per_host, ru.per_host, rs.per_host):
        assert _tup(a) == _tup(b) == _tup(c)
    for lu, ls in zip(lat_u, lat_s):
        assert np.array_equal(lu, ls)


@pytest.mark.parametrize("nh,n", [(2, N), (8, N), (32, 40)])
def test_sharded_tick_identical_multi_pod(nh, n):
    traces = _traces(nh, n)
    py = MultiHostDriver(_mounts(nh), outstanding=OUTSTANDING).run(traces)
    ru, lat_u = MultiHostReplay(
        _mounts(nh), outstanding=OUTSTANDING).run_recorded(traces)
    eng = ShardedMultiHostReplay(_mounts(nh), outstanding=OUTSTANDING)
    rs, lat_s = eng.run_recorded(traces)
    _assert_identical(py, ru, lat_u, rs, lat_s)
    mesh = eng.last_mesh
    assert mesh["device_count"] == shard_count(nh)
    assert mesh["device_count"] * mesh["hosts_per_device"] == nh


@pytest.mark.parametrize("name", ["pmem", "cxl-ssd-cache"])
def test_sharded_tick_identical_other_media(name):
    nh = 4
    traces = _traces(nh)
    py = MultiHostDriver(_mounts(nh, name),
                         outstanding=OUTSTANDING).run(traces)
    ru, lat_u = MultiHostReplay(
        _mounts(nh, name), outstanding=OUTSTANDING).run_recorded(traces)
    rs, lat_s = ShardedMultiHostReplay(
        _mounts(nh, name), outstanding=OUTSTANDING).run_recorded(traces)
    _assert_identical(py, ru, lat_u, rs, lat_s)


@pytest.mark.parametrize("return_latencies", [True, False],
                         ids=["streams", "streaming"])
def test_sharded_metrics_bundle_identical(return_latencies):
    """The sharded lane renders the exact same MetricsBundle JSON as the
    unsharded lane AND the interpreted driver (histograms, windows,
    port/QoS telemetry, media counters) in both collection modes: folded
    on the host from the owner-gated streams, or from the psum-folded
    in-scan accumulator."""
    nh = 4
    traces = _traces(nh, kind="zipfian")
    py = MultiHostDriver(_mounts(nh, qos=True), outstanding=OUTSTANDING,
                         metrics=MetricsSpec()).run(traces)
    ru = MultiHostReplay(_mounts(nh, qos=True), outstanding=OUTSTANDING,
                         metrics=MetricsSpec()).run(
        traces, return_latencies=return_latencies)
    rs = ShardedMultiHostReplay(_mounts(nh, qos=True),
                                outstanding=OUTSTANDING,
                                metrics=MetricsSpec()).run(
        traces, return_latencies=return_latencies)
    assert py.metrics.to_jsonable() == ru.metrics.to_jsonable() \
        == rs.metrics.to_jsonable()


# The sharded lane at four shards, in a child process that forces four CPU
# devices: both lanes, both collection modes, one JSON line on stdout.
_FOUR_SHARDS = r"""
import json, sys
import numpy as np
sys.path[:0] = sys.argv[1:]
from test_shard import _mounts, _traces, _tup, OUTSTANDING
from repro.core.replay import (MetricsSpec, MultiHostReplay,
                               ShardedMultiHostReplay)

nh = 8
traces = _traces(nh, n=96, kind="zipfian")
out = {}
for lat in (True, False):
    for name, cls in (("unsharded", MultiHostReplay),
                      ("sharded", ShardedMultiHostReplay)):
        eng = cls(_mounts(nh, "cxl-ssd-cache", qos=True),
                  outstanding=OUTSTANDING, metrics=MetricsSpec())
        if lat:
            res, lats = eng.run_recorded(traces)
            lats = [np.asarray(x).tolist() for x in lats]
        else:
            res, lats = eng.run(traces, return_latencies=False), None
        out[f"{name}/{lat}"] = {
            "summary": [_tup(r) for r in res.per_host]
                       + [res.elapsed_ticks],
            "latency": lats, "metrics": res.metrics.to_jsonable(),
            "mesh": getattr(eng, "last_mesh", None)}
print(json.dumps(out))
"""


def test_sharded_equals_unsharded_across_four_shards():
    """Real shard boundaries in the default tier: the election's
    all_gather and the record broadcast cross four devices, and the
    latency streams and MetricsBundle equal the unsharded lane's with and
    without per-access outputs (8 cached CXL-SSD hosts, QoS, ECMP)."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    paths = [str(Path(__file__).parent)] + [p for p in sys.path if p]
    proc = subprocess.run([sys.executable, "-c", _FOUR_SHARDS, *paths],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for lat in (True, False):
        u, s = out[f"unsharded/{lat}"], out[f"sharded/{lat}"]
        assert s["mesh"] == {"device_count": 4, "hosts_per_device": 2}
        assert u["summary"] == s["summary"]
        assert u["latency"] == s["latency"]
        assert u["metrics"] == s["metrics"]
    assert out["sharded/True"]["latency"] is not None
    assert out["sharded/True"]["metrics"] == out["sharded/False"]["metrics"]


def test_sharded_qos_tick_identical():
    nh = 8
    traces = _traces(nh, kind="bursty")
    py = MultiHostDriver(_mounts(nh, qos=True),
                         outstanding=OUTSTANDING).run(traces)
    ru, lat_u = MultiHostReplay(
        _mounts(nh, qos=True), outstanding=OUTSTANDING).run_recorded(traces)
    rs, lat_s = ShardedMultiHostReplay(
        _mounts(nh, qos=True), outstanding=OUTSTANDING).run_recorded(traces)
    _assert_identical(py, ru, lat_u, rs, lat_s)


def test_sharded_transport_faults_tick_identical():
    """Per-access fault hop columns (CRC retry stretches) shard along the
    host axis; latencies AND the fault counters must match both lanes."""
    from repro.core.faults import FaultConfig, FaultPlan, install

    nh = 4
    traces = _traces(nh)
    cfg = FaultConfig(link_retry_rate=0.25, link_retry_max=2)

    def mk():
        tgts = _mounts(nh)
        install(FaultPlan(cfg, seed=5), tgts)
        return tgts

    py = MultiHostDriver(mk(), outstanding=OUTSTANDING,
                         metrics=MetricsSpec()).run(traces)
    ru, lat_u = MultiHostReplay(mk(), outstanding=OUTSTANDING,
                                metrics=MetricsSpec()).run_recorded(traces)
    rs, lat_s = ShardedMultiHostReplay(
        mk(), outstanding=OUTSTANDING,
        metrics=MetricsSpec()).run_recorded(traces)
    _assert_identical(py, ru, lat_u, rs, lat_s)
    jp = py.metrics.to_jsonable()
    assert jp["faults"]["link_retries"] > 0
    assert jp == ru.metrics.to_jsonable() == rs.metrics.to_jsonable()


def test_sharded_nand_faults_counters_identical():
    """NAND read-retry counters live in the sharded flash state; the
    psum-folded counters must match the unsharded lane exactly."""
    from repro.core.faults import FaultConfig, FaultPlan, install

    nh = 2
    traces = _traces(nh, kind="zipfian")
    cfg = FaultConfig(nand_read_retry_rate=0.3)

    def mk():
        tgts = _mounts(nh, "cxl-ssd-cache")
        install(FaultPlan(cfg, seed=3), tgts)
        return tgts

    ru, lat_u = MultiHostReplay(mk(), outstanding=OUTSTANDING,
                                metrics=MetricsSpec()).run_recorded(traces)
    rs, lat_s = ShardedMultiHostReplay(
        mk(), outstanding=OUTSTANDING,
        metrics=MetricsSpec()).run_recorded(traces)
    ju, js = ru.metrics.to_jsonable(), rs.metrics.to_jsonable()
    assert ju["faults"]["nand_read_retries"] > 0
    assert ju == js
    for lu, ls in zip(lat_u, lat_s):
        assert np.array_equal(lu, ls)


def test_sharded_run_arrays_and_return_latencies_false():
    nh = 4
    spec = WorkloadSpec("scan", num_pages=64, stride_pages=3)
    addrs, writes = traces_np(spec, 13, nh, N)
    ru = MultiHostReplay(_mounts(nh), outstanding=OUTSTANDING).run_arrays(
        addrs, writes)
    eng = ShardedMultiHostReplay(_mounts(nh), outstanding=OUTSTANDING)
    rs = eng.run_arrays(addrs, writes)
    r0 = eng.run_arrays(addrs, writes, return_latencies=False)
    for a, b, c in zip(ru.per_host, rs.per_host, r0.per_host):
        assert _tup(a) == _tup(b) == _tup(c)


def test_sharded_ragged_lens():
    nh = 4
    traces = _traces(nh)
    traces = [t[: N - 17 * h] for h, t in enumerate(traces)]
    py = MultiHostDriver(_mounts(nh), outstanding=OUTSTANDING).run(traces)
    rs, _ = ShardedMultiHostReplay(
        _mounts(nh), outstanding=OUTSTANDING).run_recorded(traces)
    for a, b in zip(py.per_host, rs.per_host):
        assert _tup(a) == _tup(b)


def test_sharded_refusals_name_covering_lane():
    from repro.core.devices import DRAMDevice
    from repro.core.fabric import MemoryPool
    from repro.core.ssd.hil import HIL, SSDConfig

    nh = 4
    traces = _traces(nh)
    # chunked streaming
    eng = ShardedMultiHostReplay(_mounts(nh), outstanding=OUTSTANDING)
    with pytest.raises(ReplayUnsupported, match="chunk_size"):
        eng.run(traces, chunk_size=64)
    # pooled views interleave one address space across shards
    fab = Fabric.build("two_level", num_hosts=nh, num_devices=2,
                       num_leaves=2)
    pool = MemoryPool(fab, {"d0": DRAMDevice(), "d1": DRAMDevice()})
    eng = ShardedMultiHostReplay(pool.views([f"h{i}" for i in range(nh)]),
                                 outstanding=OUTSTANDING)
    with pytest.raises(ReplayUnsupported, match="unsharded MultiHostReplay"):
        eng.run(traces)
    # a shared-flash HIL couples every shard's state
    from repro.core.ssd.pal import NANDTiming

    fab = Fabric.build("two_level", num_hosts=2, num_devices=2, num_leaves=2)
    hil = HIL(SSDConfig(capacity_bytes=48 * 4096, page_bytes=4096,
                        channels=2, dies_per_channel=2, pages_per_block=8,
                        timing=NANDTiming.low_latency()))
    targets = [fab.mount(f"h{i}", f"d{i}",
                         make_device("cxl-ssd-cache",
                                     cache_cfg=DRAMCacheConfig(**CACHE_KW),
                                     hil=hil))
               for i in range(2)]
    eng = ShardedMultiHostReplay(targets, outstanding=OUTSTANDING)
    with pytest.raises(ReplayUnsupported, match="private flash"):
        eng.run(_traces(2))


def test_shard_count_largest_divisor():
    assert shard_count(8, devices=range(8)) == 8
    assert shard_count(8, devices=range(3)) == 2
    assert shard_count(6, devices=range(4)) == 3
    assert shard_count(7, devices=range(4)) == 1
    assert shard_count(4, devices=range(16)) == 4
    assert shard_count(8) == shard_count(8, devices=jax.devices())


def test_sharded_explicit_device_subset():
    nh = 4
    traces = _traces(nh)
    eng = ShardedMultiHostReplay(_mounts(nh), outstanding=OUTSTANDING,
                                 devices=jax.devices()[:1])
    rs, _ = eng.run_recorded(traces)
    assert eng.last_mesh == {"device_count": 1, "hosts_per_device": nh}
    py = MultiHostDriver(_mounts(nh), outstanding=OUTSTANDING).run(traces)
    for a, b in zip(py.per_host, rs.per_host):
        assert _tup(a) == _tup(b)


def test_host_count_sweep_sharded_matches_unsharded():
    from repro.core.replay.sweep import host_count_sweep

    nh = 8
    traces = _traces(nh)
    base = host_count_sweep(_mounts(nh), traces, [2, 4, 8],
                            outstanding=OUTSTANDING)
    info = {}
    lanes = host_count_sweep(_mounts(nh), traces, [2, 4, 8],
                             outstanding=OUTSTANDING, sharded=True,
                             info=info)
    assert info["sharded"] is True
    assert info["device_count"] * info["hosts_per_device"] == nh
    for a, b in zip(base, lanes):
        for x, y in zip(a.per_host, b.per_host):
            assert _tup(x) == _tup(y)
    # the unsharded path reports its (trivial) mesh too
    info_u = {}
    host_count_sweep(_mounts(nh), traces, [2], outstanding=OUTSTANDING,
                     info=info_u)
    assert info_u == {"sharded": False, "device_count": 1,
                      "hosts_per_device": nh}


# ------------------------------------------------- multi-pod topology unit
def test_multi_pod_topology_shape():
    topo = build_topology("multi_pod", num_pods=2, hosts_per_pod=4)
    assert len(topo.hosts) == 8 and len(topo.devices) == 8
    cores = [n for n in topo.switches if n.startswith("c")]
    assert cores, "multi-pod fabric needs a core tier"
    # hosts are block-assigned to pods; device d_i lives in the NEXT pod,
    # so every h_i -> d_i path crosses the core tier
    fab = Fabric.build("multi_pod", num_pods=2, hosts_per_pod=4)
    for i in (0, 5):
        for path in fab.paths(f"h{i}", f"d{i}"):
            assert any(n.startswith("c") for n in path), \
                f"h{i}->d{i} path never crossed the core tier: {path}"


def test_multi_pod_topology_validation():
    with pytest.raises(ValueError):
        build_topology("multi_pod", num_pods=1, hosts_per_pod=4)


def test_multi_pod_ecmp_has_route_diversity():
    fab = Fabric.build("multi_pod", ecmp=True, num_pods=2, hosts_per_pod=2,
                       num_spines=2)
    assert len(fab.paths("h0", "d0")) > 1
