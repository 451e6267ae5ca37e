"""Logical-device (LD) partitions of a memory pool.

A pool built with ``ld_bytes`` gives view ``k`` its own LD at pool address
``k * ld_bytes`` (CXL 2.0 Multi-Logical Device): the interpreted
``MultiHostDriver`` and the fused ``MultiHostReplay`` must map, refuse and
time every access identically, and a pool without ``ld_bytes`` must keep
mapping one shared address space.
"""

import numpy as np
import pytest

from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.devices import DRAMDevice, make_device
from repro.core.fabric import (Fabric, LogicalDeviceRangeError, MemoryPool,
                               PoolAddressMapper)
from repro.core.replay import MetricsSpec, MultiHostReplay
from repro.core.workloads.driver import MultiHostDriver

H = 8
N = 256
PAGES = 192                    # flash pages of the pooled device
LD_BYTES = PAGES * 4096 // H   # 24 pages an LD
SPEC = MetricsSpec()


def _ssd(frames=64):
    from repro.core.ssd.hil import SSDConfig
    from repro.core.ssd.pal import NANDTiming

    return make_device(
        "cxl-ssd-cache",
        ssd_cfg=SSDConfig(capacity_bytes=PAGES * 4096, page_bytes=4096,
                          channels=2, dies_per_channel=2, pages_per_block=8,
                          timing=NANDTiming.low_latency(),
                          hil_overhead_ns=1000.0),
        cache_cfg=DRAMCacheConfig(capacity_bytes=frames * 4096,
                                  mshr_entries=4, writeback_buffer=2))


def _ld_views(devices=None, ld_bytes=LD_BYTES, hosts=H, **pool_kw):
    devices = devices or {"d0": _ssd()}
    fab = Fabric.build("single_switch", num_hosts=hosts,
                       num_devices=len(devices))
    pool = MemoryPool(fab, devices, ld_bytes=ld_bytes, **pool_kw)
    return pool.views([f"h{i}" for i in range(hosts)])


def _traces(seed, hosts=H, n=N, pages=10, write_frac=0.5):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(hosts):
        page = rng.integers(0, pages, n)
        line = rng.integers(0, 64, n)
        wr = rng.random(n) < write_frac
        out.append([(int(p) * 4096 + int(o) * 64, 64, bool(w))
                    for p, o, w in zip(page, line, wr)])
    return out


def _recorded_python(views, traces):
    """Interpreted run with metrics, and each host's per-access latency."""
    lat = [[] for _ in views]
    for i, v in enumerate(views):
        real = v.service

        def service(now, addr, size, write, posted=False, _real=real,
                    _lat=lat[i]):
            done = _real(now, addr, size, write, posted)
            _lat.append(done - now)
            return done

        v.service = service
    res = MultiHostDriver(views, metrics=SPEC).run(traces)
    return res, [np.asarray(x, np.int64) for x in lat]


def _assert_same(py, py_lat, rp, rp_lat):
    assert py.elapsed_ticks == rp.elapsed_ticks
    for a, b in zip(py.per_host, rp.per_host):
        assert (a.accesses, a.bytes_moved, a.elapsed_ticks,
                a.sum_latency_ticks, a.end_tick) == \
            (b.accesses, b.bytes_moved, b.elapsed_ticks,
             b.sum_latency_ticks, b.end_tick)
    for a, b in zip(py_lat, rp_lat):
        np.testing.assert_array_equal(a, b)
    assert py.metrics.to_jsonable() == rp.metrics.to_jsonable()


def test_ld_pool_cached_ssd_python_equals_fused():
    """Eight hosts in eight LDs of one cached CXL-SSD behind one switch,
    with a 64-frame cache: evictions, writebacks, flash programs and GC
    all occur, and every tick, summary and bundle entry agrees."""
    traces = _traces(3)
    py, py_lat = _recorded_python(_ld_views(), traces)
    eng = MultiHostReplay(_ld_views(), metrics=SPEC)
    rp, rp_lat = eng.run_recorded(traces)
    _assert_same(py, py_lat, rp, rp_lat)
    j = rp.metrics.to_jsonable()
    media, flash = j["media"][0], j["flash"][0]
    assert media["evictions"] and media["writebacks"]
    assert flash["host_writes"] and flash["gc_runs"] and eng.last_gc_runs
    assert j["lds"] == [{"ld": k, "base": k * LD_BYTES, "bytes": LD_BYTES}
                        for k in range(H)]
    assert j["ports"]["s0->d0"]["bytes_by_host"] == \
        {f"h{k}": N * 64 for k in range(H)}


@pytest.mark.parametrize("addr", [LD_BYTES, LD_BYTES + 5 * 4096 + 64])
def test_access_outside_ld_refused_on_both_paths(addr, monkeypatch):
    """One access at or past ``ld_bytes`` (host 5, access 7): the same
    error from the interpreted view and from the fused lane, the latter
    while preparing its inputs, before anything compiles."""
    from repro.obs import scopes

    traces = _traces(4)
    traces[5][7] = (addr, 64, False)
    with pytest.raises(LogicalDeviceRangeError) as py_err:
        MultiHostDriver(_ld_views()).run(traces)

    def no_compile(*a, **k):
        raise AssertionError("the fused lane reached its compiled program")

    monkeypatch.setattr(scopes, "run", no_compile)
    with pytest.raises(LogicalDeviceRangeError) as rp_err:
        MultiHostReplay(_ld_views()).run(traces)
    assert str(py_err.value) == str(rp_err.value)
    assert "'h5'" in str(rp_err.value) and "LD5" in str(rp_err.value)


def test_last_line_of_ld_is_served():
    traces = [[(LD_BYTES - 64, 64, True), (0, 64, False)]] * 2
    py = MultiHostDriver(_ld_views(hosts=2)).run(traces)
    rp = MultiHostReplay(_ld_views(hosts=2)).run(traces)
    assert py.elapsed_ticks == rp.elapsed_ticks > 0


def test_ld_bases_compose_with_two_device_interleave():
    """LDs of 5 pages over two devices interleaved by page: LD k starts on
    device k % 2, so the base shifts which device each host page hits."""
    ld = 5 * 4096
    mk = lambda: {"d0": DRAMDevice(), "d1": DRAMDevice()}  # noqa: E731
    mapper = PoolAddressMapper(num_devices=2, mode="interleave")
    traces = _traces(6, hosts=4, n=300, pages=5, write_frac=0.3)
    views = _ld_views(mk(), ld_bytes=ld, hosts=4, mapper=mapper)
    py, py_lat = _recorded_python(views, traces)
    rp, rp_lat = MultiHostReplay(
        _ld_views(mk(), ld_bytes=ld, hosts=4, mapper=mapper),
        metrics=SPEC).run_recorded(traces)
    _assert_same(py, py_lat, rp, rp_lat)
    for k, v in enumerate(views):
        assert mapper.map(v.pool_address(0)) == ((5 * k) % 2,
                                                 (5 * k // 2) * 4096)
    ports = rp.metrics.to_jsonable()["ports"]
    assert set(ports["s0->d0"]["bytes_by_host"]) == {f"h{k}" for k in
                                                     range(4)}
    assert set(ports["s0->d1"]["bytes_by_host"]) == {f"h{k}" for k in
                                                     range(4)}


def test_pool_without_ld_bytes_is_unchanged():
    """No ``ld_bytes``: every view maps the one global address space, so a
    page host 0 brought into the cache is a hit for host 1, on both paths;
    the bundle has no LD table.  In an LD pool the same traces share
    nothing."""
    def views(ld_bytes):
        fab = Fabric.build("single_switch", num_hosts=2, num_devices=1)
        pool = MemoryPool(fab, {"d0": _ssd()}, ld_bytes=ld_bytes)
        return pool.views(["h0", "h1"])

    first = [(p * 4096, 64, False) for p in range(8)]
    later = [(p * 4096 + 64, 64, False) for p in range(8)]
    traces = [first, [(0x80000, 64, False)] * 40 + later]
    shared = views(None)
    assert [v.ld for v in shared] == [None, None]
    assert shared[1].pool_address(LD_BYTES * 4) == LD_BYTES * 4
    py = MultiHostDriver(shared, metrics=SPEC).run(traces)
    rp = MultiHostReplay(views(None), metrics=SPEC).run(traces)
    assert py.metrics.to_jsonable() == rp.metrics.to_jsonable()
    assert "lds" not in rp.metrics.to_jsonable()
    ld = MultiHostReplay(views(1 << 20), metrics=SPEC).run(traces)
    hits = lambda r: r.metrics.to_jsonable()["media"][0]["hits"]  # noqa
    assert hits(rp) >= hits(ld) + len(later)


def test_ld_count_and_size_are_checked():
    fab = Fabric.build("single_switch", num_hosts=17, num_devices=1)
    with pytest.raises(ValueError, match="ld_bytes must be positive"):
        MemoryPool(fab, {"d0": DRAMDevice()}, ld_bytes=0)
    pool = MemoryPool(fab, {"d0": DRAMDevice()}, ld_bytes=1 << 20)
    views = pool.views([f"h{i}" for i in range(16)])
    assert [v.ld_base for v in views] == [k << 20 for k in range(16)]
    with pytest.raises(ValueError, match="at most 16 logical devices"):
        pool.view("h16")


def test_sharded_lane_refuses_ld_pool_views():
    from repro.core.replay import ReplayUnsupported
    from repro.core.replay.shard import ShardedMultiHostReplay

    eng = ShardedMultiHostReplay(_ld_views(hosts=4))
    with pytest.raises(ReplayUnsupported, match="unsharded MultiHostReplay"):
        eng.run(_traces(7, hosts=4, n=16))


def test_chunked_fused_lane_equals_python_on_ld_pool():
    """The chunked multi-host scan reads the same mapped columns."""
    traces = _traces(8, n=64)
    py, py_lat = _recorded_python(_ld_views(), traces)
    rp, rp_lat = MultiHostReplay(_ld_views(), metrics=SPEC).run_recorded(
        traces, chunk_size=48)
    _assert_same(py, py_lat, rp, rp_lat)
