"""The FTL map sized by the pages a replayed trace touches.

Where the device side only compares pages (the page-register buffer and
the lru/fifo cache), every whole-trace lane hands the compiled runner a
column of dense page ids and a map of ``pow2(min(span, accesses))``
entries (:func:`repro.core.replay.spec.compact_pages`).  The traces here
put their pages far apart, so the span exceeds the access count many
times over, and every lane must still match the interpreted driver per
access: latency and each event flag, the summaries and the metrics
bundle.  Direct-mapped caches, DRAM, PMEM and the streaming lane keep the
span-sized map and the real addresses.
"""

import jax
import numpy as np
import pytest

from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.devices import make_device
from repro.core.fabric import Fabric, MemoryPool
from repro.core.replay import (MetricsSpec, MultiHostReplay, ReplayEngine,
                               ShardedMultiHostReplay, cache_design_sweep)
from repro.core.replay.spec import SSD_BUF, SSD_CACHE, compact_pages
from repro.core.workloads.driver import MultiHostDriver, TraceDriver
from repro.obs import scopes

SPEC = MetricsSpec()
N = 300                      # accesses a trace (single host)
HOSTS, HN = 4, 120           # multi-host: hosts x accesses a host
LD_BYTES = 1 << 26           # 16,384 pages an LD
SPREAD = 1 << 30             # single host: pages spread over 2^18
FRONTS = ["lru", "fifo", "buf"]


def _ssd_cfg():
    from repro.core.ssd.hil import SSDConfig
    from repro.core.ssd.pal import NANDTiming

    return SSDConfig(capacity_bytes=192 * 4096, page_bytes=4096, channels=2,
                     dies_per_channel=2, pages_per_block=8,
                     timing=NANDTiming.low_latency(), hil_overhead_ns=1000.0)


def _device(front, frames=8):
    if front == "buf":
        return make_device("cxl-ssd", ssd_cfg=_ssd_cfg(), page_registers=3)
    return make_device(
        "cxl-ssd-cache", ssd_cfg=_ssd_cfg(),
        cache_cfg=DRAMCacheConfig(capacity_bytes=frames * 4096,
                                  policy=front, mshr_entries=2,
                                  writeback_buffer=2))


def _columns(seed, n, span, pages=24):
    """``n`` accesses over ``pages`` pages drawn from ``[0, span)``."""
    rng = np.random.default_rng(seed)
    pool = rng.choice(span // 4096, pages, replace=False)
    addrs = (pool[rng.integers(0, pages, n)] * 4096
             + rng.integers(0, 64, n) * 64).astype(np.int64)
    return addrs, rng.random(n) < 0.5


def _trace(addrs, writes):
    return [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]


# --------------------------------------------------- interpreted recording
def _counters(dev):
    """The interpreted device's counters behind each fused event flag, in
    flag-bit order (bit 0 hit, 1 writeback, 2 miss or fill, 3 coalesce,
    4 stall, 5 eviction)."""
    if hasattr(dev, "cache"):
        c = dev.cache
        return (c.policy.hits, c.stats["writebacks"], c.policy.misses,
                c.stats["mshr_coalesced"], c.stats["mshr_stalls"],
                c.policy.evictions)
    s = dev.stats
    return (s["buf_hits"], s["flash_writes"],
            s["flash_reads"] + s["rmw_fills"])


def _record(target, dev):
    """Wrap ``target.service`` to log each access's latency and flags word
    from the deltas of ``dev``'s counters."""
    lat, flags = [], []
    real = target.service

    def service(now, addr, size, write, posted=False):
        before = _counters(dev)
        done = real(now, addr, size, write, posted)
        lat.append(done - now)
        flags.append(sum(1 << b for b, (x, y) in
                         enumerate(zip(before, _counters(dev))) if y > x))
        return done

    target.service = service
    return lat, flags


def _summary(r):
    return (r.accesses, r.bytes_moved, r.elapsed_ticks, r.sum_latency_ticks,
            r.end_tick)


@pytest.fixture
def runner_cfgs(monkeypatch):
    """The stack config of every compiled runner called, in call order
    (the sharded lane builds its runner instead of calling it through
    :func:`scopes.run`); ``seen.args`` keeps each call's other arguments."""
    from repro.core.replay import shard

    class Seen(list):
        pass

    seen = Seen()
    seen.args = []
    real_run, real_build = scopes.run, shard._build_runner

    def run(runner, cfg, *args):
        seen.append(getattr(cfg, "stack", cfg))
        seen.args.append(args)
        return real_run(runner, cfg, *args)

    def build(cfg, *args):
        seen.append(cfg.stack)
        seen.args.append(None)
        return real_build(cfg, *args)

    monkeypatch.setattr(scopes, "run", run)
    monkeypatch.setattr(shard, "_build_runner", build)
    return seen


def _pow2(n):
    return 1 << (int(n) - 1).bit_length()


# ------------------------------------------------------- single-host lanes
@pytest.mark.parametrize("chunk", [None, 128], ids=["one-shot", "chunked"])
@pytest.mark.parametrize("front", FRONTS)
def test_scan_lane_on_compacted_pages_equals_python(front, chunk,
                                                    runner_cfgs,
                                                    monkeypatch):
    addrs, writes = _columns(11, N, SPREAD)
    trace = _trace(addrs, writes)
    ref_dev = _device(front)
    py_lat, py_flags = _record(ref_dev, ref_dev)
    py = TraceDriver(ref_dev, metrics=SPEC).run(trace)

    flags = {}
    real_finish = ReplayEngine._finish

    def finish(self, cfg, **kw):
        flags["v"] = np.asarray(kw["flags"])
        return real_finish(self, cfg, **kw)

    monkeypatch.setattr(ReplayEngine, "_finish", finish)
    rp = ReplayEngine(_device(front), metrics=SPEC).run_arrays(
        addrs, writes, chunk_size=chunk)

    np.testing.assert_array_equal(rp.latency_ticks, py_lat)
    np.testing.assert_array_equal(flags["v"], py_flags)
    assert _summary(rp) == _summary(py)
    assert rp.metrics.to_jsonable() == py.metrics.to_jsonable()
    assert _pow2(int(addrs.max()) // 4096 + 1) == 1 << 18
    assert {c.num_pages for c in runner_cfgs} == {_pow2(N)} == {512}


@pytest.mark.parametrize("policy", ["lru", "fifo"])
def test_design_sweep_on_compacted_pages_equals_python(policy, runner_cfgs):
    addrs, writes = _columns(12, N, SPREAD)
    caps = [4, 8]
    out = cache_design_sweep(_device(policy, frames=8), addrs, writes,
                             capacity_frames=caps,
                             is_lru=[policy == "lru"] * 2)
    for k, cap in enumerate(caps):
        dev = _device(policy, frames=cap)
        lat, fl = _record(dev, dev)
        py = TraceDriver(dev).run(_trace(addrs, writes))
        np.testing.assert_array_equal(out["latency_ticks"][k], lat)
        np.testing.assert_array_equal(out["hit_flags"][k],
                                      np.asarray(fl) & 1 > 0)
        np.testing.assert_array_equal(out["evict_flags"][k],
                                      np.asarray(fl) & 2 > 0)
        assert (out["sum_latency_ticks"][k], out["elapsed_ticks"][k]) == \
            (py.sum_latency_ticks, py.elapsed_ticks)
    assert [c.num_pages for c in runner_cfgs] == [512]


# -------------------------------------------------------- multi-host lanes
def _ld_pool(front):
    fab = Fabric.build("single_switch", num_hosts=HOSTS, num_devices=1)
    pool = MemoryPool(fab, {"d0": _device(front, frames=16)},
                      ld_bytes=LD_BYTES)
    return pool.views([f"h{i}" for i in range(HOSTS)]), pool.devices


def _mounts(front):
    fab = Fabric.build("single_switch", num_hosts=HOSTS, num_devices=HOSTS)
    mounts = [fab.mount(f"h{i}", f"d{i}", _device(front))
              for i in range(HOSTS)]
    return mounts, [m.inner for m in mounts]


def _multi_traces(seed, span):
    cols = [_columns(seed + i, HN, span) for i in range(HOSTS)]
    return [_trace(a, w) for a, w in cols]


MULTI = {
    # lane: (targets, replay class, chunk size); the sharded lane takes
    # per-host mounts only (it refuses pool views)
    "one-shot": (_ld_pool, MultiHostReplay, None),
    "chunked": (_ld_pool, MultiHostReplay, 48),
    "sharded-1": (_mounts, ShardedMultiHostReplay, None),
}


@pytest.mark.parametrize("lane", list(MULTI))
@pytest.mark.parametrize("front", FRONTS)
def test_multi_host_lane_on_compacted_pages_equals_python(front, lane,
                                                          runner_cfgs):
    build, cls, chunk = MULTI[lane]
    traces = _multi_traces(21, LD_BYTES if build is _ld_pool else SPREAD)
    views, devs = build(front)
    rec = [_record(v, devs[0] if len(devs) == 1 else devs[i])
           for i, v in enumerate(views)]
    py = MultiHostDriver(views, metrics=SPEC).run(traces)

    kw = ({"devices": jax.devices()[:1]} if cls is ShardedMultiHostReplay
          else {})
    eng = cls(build(front)[0], metrics=SPEC, **kw)
    prep = eng.prepare(traces)
    span = prep[0].stack.num_pages
    who, issues, dones, lens, size, aux, bundle = eng._execute_prepared(
        prep, 0, chunk_size=chunk)
    res = eng.aggregate(who, issues, dones, lens, size)

    valid = np.arange(who.size) < int(np.asarray(lens).sum())
    for i, (lat, fl) in enumerate(rec):
        mine = valid & (np.asarray(who) == i)
        np.testing.assert_array_equal((dones - issues)[mine], lat)
        np.testing.assert_array_equal(np.asarray(aux["flags"])[mine], fl)
    assert res.elapsed_ticks == py.elapsed_ticks
    assert [_summary(r) for r in res.per_host] == \
        [_summary(r) for r in py.per_host]
    assert bundle.to_jsonable() == py.metrics.to_jsonable()
    assert span >= 1 << 15
    assert {c.num_pages for c in runner_cfgs} == {_pow2(HOSTS * HN)} == {512}


# --------------------------------------- stacks that keep the span-sized map
@pytest.mark.parametrize("kind", ["direct", "dram", "pmem"])
def test_stacks_that_compute_with_pages_keep_the_real_column(kind,
                                                             runner_cfgs):
    """A direct-mapped cache picks its frame by ``page % cap``, PMEM its
    row by the address: their runners get the span-sized map (none for
    DRAM and PMEM) and the addresses as given."""
    addrs, _ = _columns(31, N, SPREAD)
    dev = (_device("direct") if kind == "direct" else make_device(kind))
    ReplayEngine(dev).run_arrays(addrs, np.zeros(addrs.size, bool))
    (cfg,), (args,) = runner_cfgs, runner_cfgs.args
    span = _pow2(int(addrs.max()) // 4096 + 1)
    assert cfg.num_pages == (span if kind == "direct" else 0)
    np.testing.assert_array_equal(np.asarray(args[1]), addrs)
    assert compact_pages(cfg, addrs)[0] is cfg


def test_streaming_lane_keeps_the_span_sized_map(tmp_path, runner_cfgs):
    from repro.data.trace_store import TraceStore

    addrs, writes = _columns(32, N, SPREAD)
    store = TraceStore.write(tmp_path / "t", addrs, writes, size=64)
    ReplayEngine(_device("lru")).run_store(store, chunk_size=128)
    span = _pow2(int(addrs.max()) // 4096 + 1)
    assert {c.num_pages for c in runner_cfgs} == {span} != {_pow2(N)}


@pytest.mark.parametrize("kind", [SSD_CACHE, SSD_BUF])
def test_relabel_keeps_page_order_offsets_and_padding(kind):
    """Dense ids in page order, offsets kept, padding zeroed; a column
    whose map would not shrink comes back as it is."""
    from repro.core.replay.spec import StackConfig

    cfg = StackConfig(kind=kind, outstanding=1, posted_writes=True,
                      num_hops=0, num_ports=1, num_pages=1 << 20)
    addrs = np.array([[7 << 32 | 64, 3 << 12 | 128, 99],
                      [3 << 12, 9 << 40, 5]], np.int64)
    valid = np.array([[True, True, True], [True, True, False]])
    out_cfg, out = compact_pages(cfg, addrs, valid)
    assert out_cfg.num_pages == 8
    np.testing.assert_array_equal(
        out, [[2 << 12 | 64, 1 << 12 | 128, 99], [1 << 12, 3 << 12, 0]])
    small = StackConfig(kind=kind, outstanding=1, posted_writes=True,
                        num_hops=0, num_ports=1, num_pages=4)
    assert compact_pages(small, addrs[:, :2] & 0x3fff)[0] is small
