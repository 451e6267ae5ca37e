"""Observability layer: python-vs-scan metrics parity, percentile
correctness, fabric attribution, streaming-mode allocation, and the
Perfetto export.

The contract under test: with ``metrics=MetricsSpec(...)`` the fused
replay lanes emit the SAME bundle — histogram for histogram, counter for
counter — the interpreted drivers build from their live stats dicts, and
the histogram percentiles agree with ``numpy.percentile`` over the raw
latencies.
"""

import json

import numpy as np
import pytest

from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.devices import DRAMDevice, make_device
from repro.core.fabric import Fabric, MemoryPool
from repro.core.fabric.routing import flow_hash
from repro.core.fabric.switch import SwitchPort
from repro.core.replay import (MetricsSpec, MultiHostReplay, ReplayEngine,
                               ReplayUnsupported)
from repro.core.replay.metrics import (MAX_HIST_BUCKETS, bucket_bounds,
                                       bucket_index, bucket_index_jnp,
                                       percentile_from_hist)
from repro.core.workloads.driver import MultiHostDriver, TraceDriver
from repro.obs import to_perfetto, write_perfetto

CACHE_KW = dict(capacity_bytes=16 * 4096, mshr_entries=4, writeback_buffer=2)
SPEC = MetricsSpec()


def _mk(name, policy="lru"):
    if name == "cxl-ssd-cache":
        return make_device(name, cache_cfg=DRAMCacheConfig(
            policy=policy, **CACHE_KW))
    return make_device(name)


def _trace(seed, n=600, pages=48, write_frac=0.3):
    rng = np.random.default_rng(seed)
    addrs = rng.integers(0, pages, n) * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < write_frac
    return [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]


def _gc_device():
    from repro.core.ssd.hil import SSDConfig
    from repro.core.ssd.pal import NANDTiming

    cfg = SSDConfig(capacity_bytes=750 * 4096, page_bytes=4096, channels=2,
                    dies_per_channel=2, pages_per_block=8,
                    timing=NANDTiming.low_latency(), hil_overhead_ns=1000.0)
    return make_device("cxl-ssd-cache", ssd_cfg=cfg,
                       cache_cfg=DRAMCacheConfig(capacity_bytes=8 * 4096,
                                                 mshr_entries=4,
                                                 writeback_buffer=2))


def _qos_ecmp_views(num_hosts=3):
    fab = Fabric.build("spine_leaf", num_hosts=num_hosts, num_devices=2,
                       num_leaves=2, num_spines=2, ecmp=True,
                       qos_weights={"h0": 3.0, "h1": 1.0, "h2": 1.0})
    pool = MemoryPool(fab, {"d0": DRAMDevice(), "d1": DRAMDevice()})
    return pool.views([f"h{i}" for i in range(num_hosts)])


def _parity(py_res, scan_res):
    jp, js = py_res.metrics.to_jsonable(), scan_res.metrics.to_jsonable()
    assert jp == js, "python and scan metrics bundles diverged"
    return jp


# --------------------------------------------------------- direct parity
@pytest.mark.parametrize("name", ["dram", "cxl-dram", "pmem", "cxl-ssd",
                                  "cxl-ssd-cache"])
def test_metrics_parity_all_devices(name):
    trace = _trace(11)
    py = TraceDriver(_mk(name), outstanding=8, engine="python",
                     metrics=SPEC).run(trace)
    rp = ReplayEngine(_mk(name), outstanding=8, metrics=SPEC).run(trace)
    j = _parity(py, rp)
    assert j["media"][0]["accesses"] == len(trace)
    assert sum(j["hist"][0].values()) == len(trace)


def test_metrics_parity_gc_pressure():
    """Write churn past a near-full tiny flash: GC runs/erases/migrations
    and write amplification must agree counter-for-counter."""
    trace = [(p * 4096, 64, True) for p in range(750)]
    trace += _trace(13, n=60, pages=750, write_frac=1.0)
    py = TraceDriver(_gc_device(), outstanding=8, engine="python",
                     metrics=SPEC).run(trace)
    rp = ReplayEngine(_gc_device(), outstanding=8, metrics=SPEC).run(trace)
    j = _parity(py, rp)
    assert j["flash"][0]["gc_runs"] > 0
    assert py.write_amplification == rp.write_amplification > 1.0


def test_metrics_parity_multihost_qos_ecmp():
    traces = [_trace(20 + h, n=300) for h in range(3)]
    py = MultiHostDriver(_qos_ecmp_views(), outstanding=8,
                         metrics=SPEC).run(traces)
    rp = MultiHostReplay(_qos_ecmp_views(), outstanding=8,
                         metrics=SPEC).run(traces)
    j = _parity(py, rp)
    assert j["ecmp"], "spine-leaf ECMP pairs must register path choices"
    assert any(r["qos_throttle_events"] for r in j["ports"].values()), \
        "3:1:1 weights under contention must floor someone"


# ------------------------------------------------ result-surface properties
def test_result_properties_and_empty_trace_guards():
    res = TraceDriver(_mk("cxl-ssd-cache"), engine="python",
                      metrics=SPEC).run(_trace(31))
    assert res.p99_ns is not None and res.p99_ns > 0
    assert 0.0 < res.hit_rate < 1.0
    assert res.write_amplification >= 1.0
    empty = TraceDriver(_mk("dram"), engine="python", metrics=SPEC).run([])
    assert empty.avg_latency_ns == 0.0
    assert empty.p99_ns is None
    assert empty.hit_rate == 0.0
    assert empty.write_amplification == 1.0
    bare = TraceDriver(_mk("dram"), engine="python").run([])
    assert bare.avg_latency_ns == 0.0
    assert bare.p99_ns is None


def test_lane_refusal_for_metricless_engines():
    """Lanes that cannot carry the telemetry accumulators refuse loudly —
    metrics are never silently omitted."""
    for engine in ("assoc", "pallas"):
        with pytest.raises(ReplayUnsupported, match="metrics"):
            TraceDriver(_mk("dram"), engine=engine, metrics=SPEC)


# -------------------------------------------------- streaming allocation
def test_streaming_mode_allocates_buckets_not_trace():
    """``return_latencies=False`` on a cached CXL-SSD: no per-access
    arrays, O(hist_buckets + num_windows) telemetry only, scalar summary
    identical to the full run."""
    trace = _trace(41, n=2000)
    full = ReplayEngine(_mk("cxl-ssd-cache"), metrics=SPEC).run(trace)
    slim = ReplayEngine(_mk("cxl-ssd-cache"), metrics=SPEC).run(
        trace, return_latencies=False)
    assert slim.latency_ticks is None
    assert slim.hit_flags is None and slim.evict_flags is None
    mb = slim.metrics
    assert mb.hist.shape == (1, SPEC.hist_buckets)
    assert mb.windows.shape == (1, SPEC.num_windows, 4)
    assert full.metrics.to_jsonable() == mb.to_jsonable()
    for attr in ("elapsed_ticks", "sum_latency_ticks", "end_tick",
                 "accesses"):
        assert getattr(full, attr) == getattr(slim, attr)


def test_streaming_mode_multihost():
    traces = [_trace(50 + h, n=200) for h in range(3)]
    full = MultiHostReplay(_qos_ecmp_views(), metrics=SPEC).run(traces)
    slim = MultiHostReplay(_qos_ecmp_views(), metrics=SPEC).run(
        traces, return_latencies=False)
    assert full.metrics.to_jsonable() == slim.metrics.to_jsonable()
    assert full.elapsed_ticks == slim.elapsed_ticks
    for a, b in zip(full.per_host, slim.per_host):
        assert (a.elapsed_ticks, a.sum_latency_ticks, a.end_tick) == \
            (b.elapsed_ticks, b.sum_latency_ticks, b.end_tick)


# -------------------------------------------------------- fabric counters
def test_ecmp_bytes_by_host_attribution_exact():
    """Under ECMP multipath, each port's ``bytes_by_host`` must attribute
    exactly the bytes of the flows whose hash chose a path through it —
    computed here independently from the flow hashes."""
    fab = Fabric.build("spine_leaf", num_hosts=2, num_devices=2,
                      num_leaves=2, num_spines=2, ecmp=True)
    rng = np.random.default_rng(7)
    size = 64
    expected = {}
    for _ in range(200):
        host = f"h{rng.integers(0, 2)}"
        dev = f"d{rng.integers(0, 2)}"
        addr = int(rng.integers(0, 1 << 20)) * size
        fab.traverse(0, host, dev, size, line_addr=addr // 64)
        paths = fab.routing.paths(host, dev)
        path = paths[flow_hash(host, dev, addr // 64) % len(paths)] \
            if len(paths) > 1 else paths[0]
        for u, v in zip(path, path[1:]):
            key = expected.setdefault((u, v), {})
            key[host] = key.get(host, 0) + size
    for (u, v), by_host in expected.items():
        assert fab.ports[(u, v)].bytes_by_origin == by_host, f"{u}->{v}"
    # port_report surfaces the same attribution (plus the new counter)
    for row in fab.port_report(1):
        u, v = row["port"].split("->")
        assert row["bytes_by_host"] == expected[(u, v)]
        assert row["qos_throttle_events"] == 0  # no QoS weights configured
    # and the selection counts cover every multipath pair that carried flow
    assert fab.ecmp_counts
    for key, counts in fab.ecmp_counts.items():
        assert sum(counts) > 0 and len(counts) > 1


def test_qos_throttle_event_counter():
    port = SwitchPort("a", "b", bw_gbps=64.0)
    port.set_weights({"h0": 3.0, "h1": 1.0})
    assert port.qos_update(0, 64, "h1") == 0      # first arrival: no floor
    floored = 0
    for t in range(1, 20):
        floored += port.qos_update(t, 64, "h1") > 0
    assert port.qos_throttle_events == floored > 0
    port.reset()
    assert port.qos_throttle_events == 0


# ------------------------------------------------------------ percentiles
def test_percentiles_match_numpy_inverted_cdf():
    rng = np.random.default_rng(3)
    for n in (1, 2, 17, 1000):
        lat = rng.integers(0, 1 << 30, n)
        hist = np.bincount(bucket_index(lat, SPEC.hist_buckets),
                           minlength=SPEC.hist_buckets)
        for q in (50, 95, 99, 100):
            p = percentile_from_hist(hist, q)
            want = int(np.percentile(lat, q, method="inverted_cdf"))
            assert p["lo"] <= want <= p["hi"], (n, q)
            assert p["bucket"] == int(bucket_index(want, SPEC.hist_buckets))
    assert percentile_from_hist(np.zeros(16, np.int64), 99) is None


def test_bucket_index_numpy_jnp_twins_agree():
    from jax import enable_x64

    vals = np.concatenate([
        np.arange(0, 64),
        2 ** np.arange(3, 52, dtype=np.int64),
        2 ** np.arange(3, 52, dtype=np.int64) - 1,
        np.random.default_rng(5).integers(0, 1 << 52, 500)])
    with enable_x64(True):
        jidx = np.asarray(bucket_index_jnp(vals, MAX_HIST_BUCKETS))
    nidx = bucket_index(vals, MAX_HIST_BUCKETS)
    assert (jidx == nidx).all()
    # bounds invert the index: every value lies inside its bucket
    for v in vals[vals < (1 << 40)]:
        lo, hi = bucket_bounds(int(nidx[list(vals).index(v)]))
        assert lo <= int(v) <= hi


def test_metrics_spec_validation():
    with pytest.raises(ValueError):
        MetricsSpec(hist_buckets=4)
    with pytest.raises(ValueError):
        MetricsSpec(hist_buckets=MAX_HIST_BUCKETS + 1)
    with pytest.raises(ValueError):
        MetricsSpec(num_windows=0)


# -------------------------------------------------------- perfetto export
def test_perfetto_export_smoke(tmp_path):
    traces = [_trace(60 + h, n=150) for h in range(3)]
    res = MultiHostDriver(_qos_ecmp_views(), outstanding=8,
                          metrics=SPEC).run(traces)
    path = write_perfetto(res, str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert events and doc["displayTimeUnit"] == "ns"
    phases = {e["ph"] for e in events}
    assert {"M", "C", "X"} <= phases
    procs = {e["args"]["name"] for e in events if e["name"] == "process_name"}
    assert {"host h0", "host h1", "host h2", "fabric", "devices"} <= procs
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert {"bandwidth_gbps", "occupancy", "hit_rate"} == counters
    assert any(e["name"].startswith("port ") for e in events)
    assert any(e["name"].startswith("ecmp ") for e in events)
    with pytest.raises(TypeError):
        to_perfetto(object())


# --------------------------------------------------- property tests (sat.)
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    # Fixed length + bounded page pool keeps one compiled program per
    # device kind across all examples (same shape discipline as
    # test_replay's property tests).
    PAGES = st.lists(st.integers(0, 31), min_size=192, max_size=192)
    WRITES = st.lists(st.booleans(), min_size=192, max_size=192)
    OFFSETS = st.lists(st.integers(0, 63), min_size=192, max_size=192)

    @settings(max_examples=6, deadline=None)
    @given(pages=PAGES, writes=WRITES, offs=OFFSETS,
           name=st.sampled_from(["dram", "cxl-dram", "pmem", "cxl-ssd",
                                 "cxl-ssd-cache"]))
    def test_property_metrics_parity_all_devices(pages, writes, offs, name):
        trace = [(p * 4096 + o * 64, 64, w)
                 for p, o, w in zip(pages, offs, writes)]
        py = TraceDriver(_mk(name), outstanding=4, engine="python",
                         metrics=SPEC).run(trace)
        rp = ReplayEngine(_mk(name), outstanding=4, metrics=SPEC).run(trace)
        _parity(py, rp)

    @settings(max_examples=4, deadline=None)
    @given(pages=PAGES, writes=WRITES)
    def test_property_metrics_parity_multihost_qos_ecmp(pages, writes):
        traces = [[(p * 4096 + ((h * 7 + i) % 64) * 64, 64, w)
                   for i, (p, w) in enumerate(zip(pages, writes))]
                  for h in range(3)]
        py = MultiHostDriver(_qos_ecmp_views(), outstanding=4,
                             metrics=SPEC).run(traces)
        rp = MultiHostReplay(_qos_ecmp_views(), outstanding=4,
                             metrics=SPEC).run(traces)
        _parity(py, rp)

    # 600 of 750 pages pre-filled: close enough to the watermark that the
    # rewrite tail collects, far enough that greedy GC keeps up with any
    # 192-rewrite distribution (uniform spread is the worst case; tested)
    GC_PAGES = st.lists(st.integers(0, 599), min_size=192, max_size=192)

    @settings(max_examples=4, deadline=None)
    @given(pages=GC_PAGES, offs=OFFSETS)
    def test_property_metrics_parity_gc_pressure(pages, offs):
        trace = [(p * 4096, 64, True) for p in range(600)]
        trace += [(p * 4096 + o * 64, 64, True)
                  for p, o in zip(pages, offs)]
        py = TraceDriver(_gc_device(), outstanding=8, engine="python",
                         metrics=SPEC).run(trace)
        rp = ReplayEngine(_gc_device(), outstanding=8,
                          metrics=SPEC).run(trace)
        _parity(py, rp)

    LATS = st.lists(st.integers(0, (1 << 48) - 1), min_size=1, max_size=400)

    @settings(max_examples=50, deadline=None)
    @given(lat=LATS, q=st.sampled_from([50.0, 90.0, 95.0, 99.0, 99.9]))
    def test_property_percentile_contains_numpy(lat, q):
        arr = np.asarray(lat, np.int64)
        hist = np.bincount(bucket_index(arr, MAX_HIST_BUCKETS),
                           minlength=MAX_HIST_BUCKETS)
        p = percentile_from_hist(hist, q)
        want = int(np.percentile(arr, q, method="inverted_cdf"))
        assert p["lo"] <= want <= p["hi"]
        assert p["n"] == arr.size
