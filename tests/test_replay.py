"""Fused replay engine: tick-equivalence with the interpreted drivers.

The contract under test: for every supported stack, the single
``jax.lax.scan`` replay (`repro.core.replay`) produces *exactly* the same
ticks as `TraceDriver`/`MultiHostDriver` interpreting the same trace access
by access — elapsed, per-access latency sum, and completion tick all equal.
"""

import numpy as np
import pytest

from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.devices import DRAMDevice, make_device
from repro.core.fabric import Fabric, MemoryPool
from repro.core.replay import (AssocReplayEngine, MultiHostReplay,
                               ReplayEngine, ReplayUnsupported, busy_until,
                               port_busy_until)
from repro.core.workloads.driver import MultiHostDriver, TraceDriver

# One cache geometry reused everywhere so the jitted replay program is
# compiled once per policy, not once per test.
CACHE_KW = dict(capacity_bytes=16 * 4096, mshr_entries=4, writeback_buffer=2)
N = 1500


def _mk(name, policy="lru"):
    if name == "cxl-ssd-cache":
        return make_device(name, cache_cfg=DRAMCacheConfig(
            policy=policy, **CACHE_KW))
    return make_device(name)


def _trace(seed, n=N, pages=48, write_frac=0.3):
    rng = np.random.default_rng(seed)
    addrs = rng.integers(0, pages, n) * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < write_frac
    return [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]


def _assert_equal(py, rp):
    assert py.accesses == rp.accesses
    assert py.bytes_moved == rp.bytes_moved
    assert py.elapsed_ticks == rp.elapsed_ticks
    assert py.sum_latency_ticks == rp.sum_latency_ticks
    assert py.end_tick == rp.end_tick


# ------------------------------------------------------------ single host
@pytest.mark.parametrize("name", ["dram", "cxl-dram", "pmem", "cxl-ssd",
                                  "cxl-ssd-cache"])
def test_scan_matches_python_all_devices(name):
    trace = _trace(1)
    py = TraceDriver(_mk(name), outstanding=8).run(trace)
    rp = ReplayEngine(_mk(name), outstanding=8).run(trace)
    _assert_equal(py, rp)


@pytest.mark.parametrize("policy", ["lru", "fifo", "direct"])
def test_cached_policies_exact(policy):
    trace = _trace(2, write_frac=0.5)
    py = TraceDriver(_mk("cxl-ssd-cache", policy), outstanding=8).run(trace)
    rp = ReplayEngine(_mk("cxl-ssd-cache", policy), outstanding=8).run(trace)
    _assert_equal(py, rp)
    # hit accounting agrees with the policy objects
    dev = _mk("cxl-ssd-cache", policy)
    TraceDriver(dev, outstanding=8).run(trace)
    assert rp.hits == dev.cache.policy.hits


def test_cached_stress_minimal_buffers():
    """mshr=1 / wb=1 maximizes stall interleavings; posted_writes=False and
    outstanding=1 exercise the other driver branches."""
    cfg = DRAMCacheConfig(capacity_bytes=8 * 4096, policy="lru",
                          mshr_entries=1, writeback_buffer=1)
    trace = _trace(3, write_frac=0.6)
    for kw in (dict(posted_writes=False), dict(outstanding=1)):
        py = TraceDriver(make_device("cxl-ssd-cache", cache_cfg=cfg),
                         **kw).run(trace)
        rp = ReplayEngine(make_device("cxl-ssd-cache", cache_cfg=cfg),
                          **kw).run(trace)
        _assert_equal(py, rp)


def test_start_tick_offset():
    trace = _trace(4)
    py = TraceDriver(_mk("cxl-dram"), outstanding=8).run(trace, start_tick=12345)
    rp = ReplayEngine(_mk("cxl-dram"), outstanding=8).run(trace, start_tick=12345)
    _assert_equal(py, rp)


# ----------------------------------------------------------------- fabric
@pytest.mark.parametrize("name", ["dram", "cxl-ssd-cache"])
def test_fabric_mounted_exact(name):
    trace = _trace(5)

    def mk():
        fab = Fabric.build("two_level", num_hosts=2, num_devices=2,
                           num_leaves=2)
        return fab.mount("h1", "d1", _mk(name))

    py = TraceDriver(mk(), outstanding=8).run(trace)
    rp = ReplayEngine(mk(), outstanding=8).run(trace)
    _assert_equal(py, rp)


def _pool_views(nh=4):
    fab = Fabric.build("single_switch", num_hosts=4, num_devices=1)
    pool = MemoryPool(fab, {"d0": DRAMDevice()})
    return pool.views([f"h{i}" for i in range(nh)])


def test_multihost_exact_pooled():
    traces = [_trace(10 + h, n=1000) for h in range(4)]
    py = MultiHostDriver(_pool_views()).run(traces)
    rp = MultiHostReplay(_pool_views()).run(traces)
    assert py.elapsed_ticks == rp.elapsed_ticks
    for a, b in zip(py.per_host, rp.per_host):
        _assert_equal(a, b)


def test_multihost_exact_private_mounts():
    def mk():
        fab = Fabric.build("direct", num_pairs=2)
        return [fab.mount(f"h{i}", f"d{i}", DRAMDevice()) for i in range(2)]

    traces = [_trace(20, n=800), _trace(21, n=600)]
    py = MultiHostDriver(mk()).run(traces)
    rp = MultiHostReplay(mk()).run(traces)
    assert py.elapsed_ticks == rp.elapsed_ticks
    for a, b in zip(py.per_host, rp.per_host):
        _assert_equal(a, b)


# --------------------------------------------------------------- dispatch
def test_driver_engine_dispatch():
    trace = _trace(6)
    py = TraceDriver(_mk("cxl-ssd-cache")).run(trace)
    sc = TraceDriver(_mk("cxl-ssd-cache"), engine="scan").run(trace)
    _assert_equal(py, sc)
    with pytest.raises(ValueError):
        TraceDriver(_mk("dram"), engine="warp")


def test_driver_scan_falls_back_to_multihost_for_pool_views():
    trace = _trace(7, n=800)
    py = TraceDriver(_pool_views(1)[0]).run(trace)
    rp = TraceDriver(_pool_views(1)[0], engine="scan").run(trace)
    _assert_equal(py, rp)


def test_multihost_driver_scan_engine():
    traces = [_trace(30 + h, n=700) for h in range(4)]
    py = MultiHostDriver(_pool_views()).run(traces)
    rp = MultiHostDriver(_pool_views(), engine="scan").run(traces)
    assert py.elapsed_ticks == rp.elapsed_ticks


def test_unsupported_shapes_raise():
    # 2Q policy has no vectorized form
    dev = make_device("cxl-ssd-cache",
                      cache_cfg=DRAMCacheConfig(policy="2q", **{
                          k: v for k, v in CACHE_KW.items()}))
    with pytest.raises(ReplayUnsupported):
        ReplayEngine(dev).run(_trace(8, n=64))
    # non-uniform access size
    with pytest.raises(ReplayUnsupported):
        ReplayEngine(_mk("dram")).run([(0, 64, False), (64, 128, False)])
    # line-crossing access
    with pytest.raises(ReplayUnsupported):
        ReplayEngine(_mk("dram")).run([(32, 64, False)])
    # used device (state would not match a fresh snapshot)
    dev = _mk("dram")
    dev.service(0, 0, 64, False)
    with pytest.raises(ReplayUnsupported):
        ReplayEngine(dev).run(_trace(8, n=64))


def test_empty_trace_refused_on_array_entry_points():
    empty = np.array([], np.int64)
    nowrites = np.array([], bool)
    with pytest.raises(ReplayUnsupported, match="empty"):
        ReplayEngine(_mk("dram")).run_arrays(empty, nowrites)
    with pytest.raises(ReplayUnsupported, match="empty"):
        AssocReplayEngine(_mk("dram")).run_arrays(empty, nowrites)


def test_fabric_with_prior_traffic_raises():
    """Shared ports carry busy-until state from other mounts; a zeroed
    replay would silently diverge, so it must refuse instead."""
    fab = Fabric.build("two_level", num_hosts=2, num_devices=2, num_leaves=1)
    other = fab.mount("h0", "d0", DRAMDevice())
    target = fab.mount("h1", "d1", DRAMDevice())
    TraceDriver(other).run(_trace(70, n=64))     # dirties the shared spine
    with pytest.raises(ReplayUnsupported):
        ReplayEngine(target).run(_trace(71, n=64))


def test_pallas_overflow_guard():
    from repro.core.replay.pallas_engine import run_pallas

    n = 12_000_000   # worst-case > 2^31 ns on the default timing model
    with pytest.raises(ReplayUnsupported):
        run_pallas(_mk("cxl-ssd-cache"), np.zeros(n, np.int64),
                   np.zeros(n, bool))
    # page ids past the kernel's int32 tag range must refuse, not collide
    with pytest.raises(ReplayUnsupported):
        run_pallas(_mk("cxl-ssd-cache"),
                   np.asarray([(5 + 2**32) * 4096], np.int64),
                   np.zeros(1, bool))


# ------------------------------------------------------------------ pallas
def test_pallas_engine_decisions_match_oracle():
    from repro.core.cache.trace_sim import TraceCacheSim

    trace = _trace(9)
    pages = np.asarray([a // 4096 for a, _, _ in trace], np.int32)
    writes = np.asarray([w for _, _, w in trace])
    res = TraceDriver(_mk("cxl-ssd-cache"), engine="pallas").run(trace)
    frames = CACHE_KW["capacity_bytes"] // 4096
    hits, evicts, _ = TraceCacheSim(num_sets=1, ways=frames,
                                    policy="lru").run(pages, writes)
    assert (np.asarray(hits) == res.hit_flags).all()
    assert (np.asarray(evicts) == res.evict_flags).all()


def test_pallas_fused_kernel_matches_ref():
    from repro.kernels.cache_sim import cache_sim_fused
    from repro.kernels.ref import cache_sim_fused_ref

    rng = np.random.default_rng(40)
    pages = rng.integers(0, 256, 4000).astype(np.int32)
    writes = rng.random(4000) < 0.4
    kw = dict(num_sets=16, ways=4, policy="fifo", outstanding=4, issue_ns=3,
              hit_ns=50, miss_ns=5213, miss_occ_ns=213, wb_ns=87)
    h1, e1, l1, _ = cache_sim_fused(pages, writes, **kw)
    h2, e2, l2 = cache_sim_fused_ref(pages, writes, **kw)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


# ------------------------------------------------------------------ sweeps
def test_cache_design_sweep_lanes_match_single_runs():
    from repro.core.replay.sweep import cache_design_sweep

    rng = np.random.default_rng(41)
    addrs = (rng.integers(0, 24, 1200) * 4096
             + rng.integers(0, 64, 1200) * 64).astype(np.int64)
    writes = rng.random(1200) < 0.3
    caps = [4, 16, 8]
    lrus = [True, False, True]
    base = make_device("cxl-ssd-cache", cache_cfg=DRAMCacheConfig(
        capacity_bytes=16 * 4096, mshr_entries=4, writeback_buffer=2))
    out = cache_design_sweep(base, addrs, writes, capacity_frames=caps,
                             is_lru=lrus)
    for k, (c, l) in enumerate(zip(caps, lrus)):
        cfg = DRAMCacheConfig(capacity_bytes=c * 4096,
                              policy="lru" if l else "fifo",
                              mshr_entries=4, writeback_buffer=2)
        r = ReplayEngine(make_device("cxl-ssd-cache", cache_cfg=cfg)) \
            .run_arrays(addrs, writes)
        assert int(out["sum_latency_ticks"][k]) == r.sum_latency_ticks
        assert (out["hit_flags"][k] == r.hit_flags).all()


def test_host_count_sweep_matches_python_driver():
    from repro.core.replay.sweep import host_count_sweep

    traces = [_trace(50 + h, n=700) for h in range(4)]
    lanes = host_count_sweep(_pool_views(), traces, [1, 2, 4])
    for h, lane in zip([1, 2, 4], lanes):
        py = MultiHostDriver(_pool_views(h)).run(traces[:h])
        assert py.elapsed_ticks == lane.elapsed_ticks
        for a, b in zip(py.per_host, lane.per_host[:h]):
            _assert_equal(a, b)


# --------------------------------------------------- property test (sat.)
# Property tests need hypothesis (a dev extra); they skip cleanly when absent.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    # Fixed length + bounded page pool keeps one compiled program per device
    # kind across all examples.
    PAGES = st.lists(st.integers(0, 31), min_size=256, max_size=256)
    WRITES = st.lists(st.booleans(), min_size=256, max_size=256)
    OFFSETS = st.lists(st.integers(0, 63), min_size=256, max_size=256)

    @settings(max_examples=8, deadline=None)
    @given(pages=PAGES, writes=WRITES, offs=OFFSETS,
           name=st.sampled_from(["dram", "cxl-dram", "pmem", "cxl-ssd",
                                 "cxl-ssd-cache"]))
    def test_property_scan_matches_python_all_configs(pages, writes, offs,
                                                      name):
        trace = [(p * 4096 + o * 64, 64, w)
                 for p, o, w in zip(pages, offs, writes)]
        py = TraceDriver(_mk(name), outstanding=4).run(trace)
        rp = ReplayEngine(_mk(name), outstanding=4).run(trace)
        _assert_equal(py, rp)


# --------------------------------------------------------- CI smoke (sat.)
@pytest.mark.slow
def test_replay_smoke_all_engines():
    """Benchmark smoke: tiny trace through every engine lane.  scan,
    blocked scan and assoc must be tick-exact; pallas must agree on
    hit/evict decisions with the cache oracle.  (Gated behind the slow
    marker; CI runs it in a dedicated job.)"""
    from repro.core.cache.trace_sim import TraceCacheSim

    trace = _trace(60, n=512)
    py = TraceDriver(_mk("cxl-ssd-cache")).run(trace)
    sc = TraceDriver(_mk("cxl-ssd-cache"), engine="scan").run(trace)
    _assert_equal(py, sc)
    bl = TraceDriver(_mk("cxl-ssd-cache"), engine="scan",
                     block_size=8).run(trace)
    _assert_equal(py, bl)
    py_d = TraceDriver(_mk("dram")).run(trace)
    av = TraceDriver(_mk("dram"), engine="assoc").run(trace)
    _assert_equal(py_d, av)
    pl_res = TraceDriver(_mk("cxl-ssd-cache"), engine="pallas").run(trace)
    pages = np.asarray([a // 4096 for a, _, _ in trace], np.int32)
    writes = np.asarray([w for _, _, w in trace])
    hits, _, _ = TraceCacheSim(num_sets=1,
                               ways=CACHE_KW["capacity_bytes"] // 4096,
                               policy="lru").run(pages, writes)
    assert (np.asarray(hits) == pl_res.hit_flags).all()


# ----------------------------------------- assoc lane (log-depth replay)
def test_assoc_matches_python_stateless_devices():
    """The associative lane is tick-identical on bandwidth-bound DRAM/PMEM
    replays (outstanding=32: the streaming regime the drivers are sized
    for)."""
    trace = _trace(80)
    for name in ("dram", "pmem"):
        for st in (0, 12345):
            py = TraceDriver(_mk(name)).run(trace, start_tick=st)
            rp = AssocReplayEngine(_mk(name)).run(trace, start_tick=st)
            _assert_equal(py, rp)


def test_assoc_pmem_row_hits_exact():
    """Row-buffer locality is elementwise data in the assoc lane; a
    line-sequential trace exercises it heavily."""
    trace = [(i * 64, 64, i % 3 == 0) for i in range(1200)]
    dev = _mk("pmem")
    py = TraceDriver(dev).run(trace)
    rp = AssocReplayEngine(_mk("pmem")).run(trace)
    _assert_equal(py, rp)
    assert dev.stats["row_hits"] > 0
    assert int(rp.hit_flags.sum()) == dev.stats["row_hits"]


def test_assoc_non_posted_writes_exact():
    trace = _trace(81, write_frac=0.5)
    py = TraceDriver(_mk("dram"), posted_writes=False).run(trace)
    rp = AssocReplayEngine(_mk("dram"), posted_writes=False).run(trace)
    _assert_equal(py, rp)


def test_assoc_refuses_latency_bound_instead_of_diverging():
    """A small LFB makes the completion feedback chain through the whole
    trace; the Kleene budget runs out and the lane must refuse — never
    return an uncertified result."""
    with pytest.raises(ReplayUnsupported, match="not certified"):
        AssocReplayEngine(_mk("cxl-dram"), outstanding=4).run(_trace(82))


def test_assoc_refuses_stateful_media():
    for name in ("cxl-ssd", "cxl-ssd-cache"):
        with pytest.raises(ReplayUnsupported, match="per-access state"):
            AssocReplayEngine(_mk(name)).run(_trace(83, n=64))


def test_assoc_refuses_ecmp_routes():
    fab = Fabric.build("spine_leaf", num_hosts=1, num_devices=1,
                       num_leaves=2, num_spines=3, ecmp=True)
    target = fab.mount("h0", "d0", DRAMDevice())
    with pytest.raises(ReplayUnsupported, match="ECMP"):
        AssocReplayEngine(target).run(_trace(84, n=64))


def test_driver_assoc_engine_dispatch():
    trace = _trace(85)
    py = TraceDriver(_mk("dram")).run(trace)
    ap = TraceDriver(_mk("dram"), engine="assoc").run(trace)
    _assert_equal(py, ap)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_assoc_solver_backends_agree(backend):
    """The solver core is one formula set behind an ops shim; both the
    numpy (CPU) and eager-jnp (accelerator) instantiations must be
    tick-identical to the interpreted driver."""
    trace = _trace(89, n=900)
    for name in ("dram", "pmem"):
        py = TraceDriver(_mk(name)).run(trace)
        rp = AssocReplayEngine(_mk(name), backend=backend).run(trace)
        _assert_equal(py, rp)


def test_local_sort_equals_full_sort_for_bounded_displacement():
    """The accelerator path's two-pass block sort: exact on any stream
    whose elements sit within block//2 of their sorted slot (the
    completion-stream shape: monotone chain + bounded tails)."""
    from jax import enable_x64

    from repro.core.replay.assoc import _local_sort

    rng = np.random.default_rng(7)
    with enable_x64(True):
        for _ in range(20):
            n = int(rng.integers(5, 700))
            occ = int(rng.integers(1, 40))
            spread = int(rng.integers(0, 1500))
            base = np.cumsum(rng.integers(occ, occ + 25, n))
            x = (base + rng.integers(0, spread + 1, n)).astype(np.int64)
            block = max(8, 2 * (spread // occ + 1))
            got = np.asarray(_local_sort(x, block))
            np.testing.assert_array_equal(got, np.sort(x))


# ------------------------------------------------- blocked replay (B > 1)
def test_block_size_invariance():
    """B in {1, 8, 64, len(trace)}: the carry crosses block seams
    untouched, so every block size is tick-identical."""
    trace = _trace(86, n=80)
    py = TraceDriver(_mk("cxl-dram"), outstanding=8).run(trace)
    for b in (1, 8, 64, len(trace)):
        rp = ReplayEngine(_mk("cxl-dram"), outstanding=8,
                          block_size=b).run(trace)
        _assert_equal(py, rp)


def test_blocked_stateful_stack_exact():
    trace = _trace(87, n=600, write_frac=0.5)
    py = TraceDriver(_mk("cxl-ssd-cache"), outstanding=8).run(trace)
    rp = ReplayEngine(_mk("cxl-ssd-cache"), outstanding=8,
                      block_size=8).run(trace)
    _assert_equal(py, rp)


def test_block_size_validated():
    with pytest.raises(ValueError):
        ReplayEngine(_mk("dram"), block_size=0)
    with pytest.raises(ValueError):
        TraceDriver(_mk("dram"), engine="scan", block_size=-3)
    # blocking only shapes the scan lowering; other engines refuse loudly
    # instead of silently ignoring the knob
    for eng in ("python", "assoc", "pallas"):
        with pytest.raises(ValueError, match="engine='scan'"):
            TraceDriver(_mk("dram"), engine=eng, block_size=8)
    with pytest.raises(ValueError, match="engine='scan'"):
        MultiHostDriver([_mk("dram")], engine="python", block_size=8)


def test_multihost_blocked_seam_reproduces_issue_race_ties():
    """Satellite regression: identical per-host traces tie the
    earliest-candidate-host race on EVERY step, so host selection relies
    purely on the lowest-index tie-break; with block_size=7 over 3x30
    steps the seams land mid-tie (step 7, 14, ... are all ties).  The
    blocked multi-host scan must reproduce the interpreted race exactly
    across those seams."""
    tr = _trace(88, n=30)
    traces = [list(tr) for _ in range(3)]

    def views():
        fab = Fabric.build("single_switch", num_hosts=3, num_devices=1)
        pool = MemoryPool(fab, {"d0": DRAMDevice()})
        return pool.views(["h0", "h1", "h2"])

    py = MultiHostDriver(views()).run(traces)
    for b in (1, 7):
        rp = MultiHostReplay(views(), block_size=b).run(traces)
        _assert_multi_equal(py, rp)
    # the tie-break really is exercised: every host issued work
    assert all(h.accesses == 30 for h in py.per_host)


# ----------------------------------------- stacked state + GC (tentpole)
def _gc_ssd_cfg(cap_pages=750):
    from repro.core.ssd.hil import SSDConfig
    from repro.core.ssd.pal import NANDTiming

    return SSDConfig(capacity_bytes=cap_pages * 4096, page_bytes=4096,
                     channels=2, dies_per_channel=2, pages_per_block=8,
                     timing=NANDTiming.low_latency(), hil_overhead_ns=1000.0)


def _gc_device(cap_pages=750):
    return make_device("cxl-ssd-cache", ssd_cfg=_gc_ssd_cfg(cap_pages),
                       cache_cfg=DRAMCacheConfig(capacity_bytes=8 * 4096,
                                                 mshr_entries=4,
                                                 writeback_buffer=2))


def _gc_trace():
    """Near-full sequential fill, then scattered rewrites — one per flash
    block, so GC victims carry ~7 valid pages and the migration path
    (read + re-program + map move) actually runs."""
    trace = [(p * 4096, 64, True) for p in range(750)]
    for k in range(40):
        trace.append((((k * 9) % 750) * 4096 + (k % 64) * 64, 64, True))
    return trace


def test_gc_pressure_scan_exact():
    """The tentpole acceptance case: a GC-triggering trace that previously
    fell back to python replays tick-identically in the scan, migrations
    included, and the collection count matches the interpreted FTL."""
    dev = _gc_device()
    py = TraceDriver(dev, outstanding=8).run(_gc_trace())
    st = dev.hil.ftl.stats
    assert st["gc_runs"] > 0 and st["gc_writes"] > 0   # migrations ran
    rp = ReplayEngine(_gc_device(), outstanding=8).run(_gc_trace())
    _assert_equal(py, rp)
    assert rp.gc_runs == st["gc_runs"]


def test_gc_churn_scan_exact():
    """Write-heavy churn over a small working set: many collections, all
    with fully-invalid victims (the steady-state shape)."""
    rng = np.random.default_rng(0)
    n = 600
    addrs = rng.integers(0, 24, n) * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < 0.7
    trace = [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]
    dev = _gc_device(cap_pages=96)
    py = TraceDriver(dev, outstanding=8).run(trace)
    assert dev.hil.ftl.stats["gc_runs"] > 0
    rp = ReplayEngine(_gc_device(cap_pages=96), outstanding=8).run(trace)
    _assert_equal(py, rp)
    assert rp.gc_runs == dev.hil.ftl.stats["gc_runs"]


def test_gc_overfill_refuses_like_python_raises():
    """Live data beyond physical capacity: the interpreted FTL raises
    "out of space"; the scan surfaces the same condition as a refusal via
    the sticky bad flag — never a silently wrong replay.  The vmapped
    cache sweep must refuse lane-wise the same way."""
    from repro.core.replay.sweep import cache_design_sweep

    bad = [(p * 4096, 64, True) for p in range(1100)]
    with pytest.raises(RuntimeError, match="out of space"):
        TraceDriver(_gc_device(), outstanding=8).run(bad)
    with pytest.raises(ReplayUnsupported, match="free blocks"):
        ReplayEngine(_gc_device(), outstanding=8).run(bad)
    addrs = np.asarray([a for a, _, _ in bad], np.int64)
    writes = np.ones(len(bad), bool)
    with pytest.raises(ReplayUnsupported, match="free blocks"):
        cache_design_sweep(_gc_device(), addrs, writes,
                           capacity_frames=[8, 4], is_lru=[True, True])


def test_gc_block_size_invariance():
    """B in {1, 8, len}: the stacked GC state crosses block seams in the
    carry untouched, so blocked replay stays tick-identical on the
    GC-capable lane."""
    # real collections crossing block seams (B=8 over ~30 GCs)
    rng = np.random.default_rng(0)
    n = 600
    addrs = rng.integers(0, 24, n) * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < 0.7
    churn = [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]
    dev = _gc_device(cap_pages=96)
    py = TraceDriver(dev, outstanding=8).run(churn)
    assert dev.hil.ftl.stats["gc_runs"] > 0
    rp = ReplayEngine(_gc_device(cap_pages=96), outstanding=8,
                      block_size=8).run(churn)
    _assert_equal(py, rp)
    # whole-trace unroll (B=len): a short write-heavy trace on a tiny
    # flash still *selects* the GC-capable stack (headroom check), and
    # len copies of its step must stay compilable and tick-identical
    short = churn[:64]
    from repro.core.replay.spec import build_stack
    cfg, _ = build_stack(_gc_device(cap_pages=48), size=64, outstanding=8,
                         issue_overhead_ns=0.5, posted_writes=True,
                         n_accesses=len(short), max_addr=23 * 4096 + 63 * 64)
    assert cfg.gc, "short trace must still select the GC-capable lane"
    py = TraceDriver(_gc_device(cap_pages=48), outstanding=8).run(short)
    for b in (1, 8, len(short)):
        rp = ReplayEngine(_gc_device(cap_pages=48), outstanding=8,
                          block_size=b).run(short)
        _assert_equal(py, rp)


# ------------------------------------- multi-host stacked media (tentpole)
def _cached_mounts(nh=2, shared_hil=False, policy="lru"):
    from repro.core.devices import CachedCXLSSDDevice
    from repro.core.ssd.hil import HIL

    fab = Fabric.build("two_level", num_hosts=nh, num_devices=nh,
                       num_leaves=2)
    hil = HIL(_gc_ssd_cfg(96)) if shared_hil else None
    out = []
    for i in range(nh):
        if shared_hil:
            dev = CachedCXLSSDDevice(cache_cfg=DRAMCacheConfig(
                policy=policy, **CACHE_KW), hil=hil)
        else:
            dev = _mk("cxl-ssd-cache", policy)
        out.append(fab.mount(f"h{i}", f"d{i}", dev))
    return out, hil


def _cached_pool(nh=4):
    # fixed 4-host fabric regardless of nh: host-count comparisons must
    # share one topology (the sweep masks hosts, it doesn't rewire)
    fab = Fabric.build("two_level", num_hosts=4, num_devices=2,
                       num_leaves=2)
    pool = MemoryPool(fab, {"d0": _mk("cxl-ssd-cache"),
                            "d1": _mk("cxl-ssd-cache")})
    return pool.views([f"h{i}" for i in range(nh)])


def test_multihost_cached_mounts_exact():
    traces = [_trace(90, n=500), _trace(91, n=400)]
    py = MultiHostDriver(_cached_mounts()[0]).run(traces)
    rp = MultiHostReplay(_cached_mounts()[0]).run(traces)
    _assert_multi_equal(py, rp)


def test_multihost_cached_pool_exact():
    traces = [_trace(92 + h, n=400) for h in range(4)]
    py = MultiHostDriver(_cached_pool()).run(traces)
    rp = MultiHostReplay(_cached_pool()).run(traces)
    _assert_multi_equal(py, rp)


def test_multihost_shared_flash_gc_exact():
    """The acceptance criterion: per-host private DRAM caches over ONE
    shared flash (CachedCXLSSDDevice(hil=...)), on a GC-triggering
    write-heavy mix — tick-identical to the interpreted driver, same
    collection count, contention through the shared FTL/PAL state."""
    traces = [_trace(95 + h, n=400, pages=24, write_frac=0.7)
              for h in range(2)]
    targets, hil = _cached_mounts(shared_hil=True)
    py = MultiHostDriver(targets).run(traces)
    assert hil.ftl.stats["gc_runs"] > 0
    eng = MultiHostReplay(_cached_mounts(shared_hil=True)[0])
    rp = eng.run(traces)
    _assert_multi_equal(py, rp)
    assert eng.last_gc_runs == hil.ftl.stats["gc_runs"]


def test_multihost_cached_block_size_invariance():
    # B=70 is the whole-trace unroll (sum of lens); keep it small — each
    # unrolled step clones the cache-miss cond into one XLA graph
    traces = [_trace(97, n=40), _trace(98, n=30)]
    py = MultiHostDriver(_cached_mounts()[0]).run(traces)
    for b in (1, 8, 70):
        rp = MultiHostReplay(_cached_mounts()[0], block_size=b).run(traces)
        _assert_multi_equal(py, rp)


# ------------------------------------ miss-path gates, access by access
# A 4-frame cache with 2 MSHRs and 1 writeback slot on a hot/cold mix:
# every gate of the miss path fires (MSHR stall and kill, clean and dirty
# evictions, a full writeback queue, coalesced loads and stores).
GATE_KW = dict(capacity_bytes=4 * 4096, mshr_entries=2, writeback_buffer=1)
# per-access events, in the order of the scan's flag bits 0..5
GATE_EVENTS = ("hits", "writebacks", "misses", "mshr_coalesced",
               "mshr_stalls", "evictions")
GATE_CASES = [(policy, wf, lane)
              for policy in ("lru", "fifo", "direct")
              for wf in (0.0, 0.5, 1.0)
              for lane in ("scan", "sweep", "multihost")
              # the sweep's policy axis is lru/fifo
              if not (lane == "sweep" and policy == "direct")]


class _EventLog:
    """An interpreted target that logs, per access, the latency and the
    cache counters the access moved."""

    def __init__(self, target, dev):
        self.target, self.dev, self.rows = target, dev, []

    def service(self, now, addr, size, write, posted=False):
        from repro.core.replay.metrics import media_counters_of

        before = media_counters_of(self.dev)
        done = self.target.service(now, addr, size, write, posted)
        after = media_counters_of(self.dev)
        self.rows.append([done - now]
                         + [after[k] - before[k] for k in GATE_EVENTS])
        return done


def _gate_dev(policy):
    return make_device("cxl-ssd-cache", cache_cfg=DRAMCacheConfig(
        policy=policy, **GATE_KW))


def _gate_trace(seed, write_frac, n=384, pages=24, hot=3):
    """Half the accesses reuse 3 hot pages back to back (coalesced loads
    and stores), half spread over 24 pages (misses, evictions)."""
    rng = np.random.default_rng(seed)
    page = np.where(rng.random(n) < 0.5, rng.integers(0, hot, n),
                    rng.integers(0, pages, n))
    addrs = (page * 4096 + rng.integers(0, 64, n) * 64).astype(np.int64)
    return addrs, rng.random(n) < write_frac


def _gate_mounts(policy, nh=2):
    fab = Fabric.build("two_level", num_hosts=nh, num_devices=nh,
                       num_leaves=2)
    return [fab.mount(f"h{i}", f"d{i}", _gate_dev(policy))
            for i in range(nh)]


def _gate_rows(targets, devs, rows):
    logs = [_EventLog(t, d) for t, d in zip(targets, devs)]
    MultiHostDriver(logs, outstanding=8).run(rows)
    return [np.asarray(log.rows, np.int64) for log in logs]


@pytest.mark.parametrize("policy,write_frac,lane", GATE_CASES)
def test_miss_path_gates_exact_per_access(policy, write_frac, lane):
    """Every lane that steps a cached CXL-SSD matches the interpreted
    device access for access: latency, hit, writeback and (where the lane
    reports them) miss, coalesce, MSHR stall and eviction."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    from repro.core.replay import MetricsSpec
    from repro.core.replay.engine import _run_stack
    from repro.core.replay.metrics import media_counters_of
    from repro.core.replay.spec import build_stack
    from repro.core.replay.sweep import cache_design_sweep

    hosts = 2 if lane == "multihost" else 1
    traces = [_gate_trace(170 + h, write_frac) for h in range(hosts)]
    tuples = [[(int(a), 64, bool(w)) for a, w in zip(*tr)] for tr in traces]
    if lane == "multihost":
        targets = _gate_mounts(policy)
        devs = [t.inner for t in targets]
    else:
        targets = devs = [_gate_dev(policy)]
    rows = _gate_rows(targets, devs, tuples)
    events = dict(zip(GATE_EVENTS, sum(r[:, 1:].sum(0) for r in rows)))
    assert events["mshr_stalls"] and events["mshr_coalesced"]
    assert events["evictions"] > events["writebacks"] or write_frac == 1.0
    assert (events["writebacks"] > 0) == (write_frac > 0)

    addrs, writes = traces[0]
    if lane == "scan":
        with enable_x64(True):
            cfg, params = build_stack(
                _gate_dev(policy), size=64, outstanding=8,
                issue_overhead_ns=0.5, posted_writes=True,
                n_accesses=addrs.size,
                max_addr=int(addrs.max()), counters=True)
            issues, dones, flags, _, _ = _run_stack(
                cfg, jax.tree.map(jnp.asarray, params), jnp.asarray(addrs),
                jnp.asarray(writes), jnp.asarray(0, jnp.int64), 1,
                MetricsSpec(), True, 64)
        got = np.column_stack(
            [np.asarray(dones) - np.asarray(issues)]
            + [(np.asarray(flags) >> b) & 1 for b in range(6)])
        np.testing.assert_array_equal(got, rows[0])
    elif lane == "sweep":
        out = cache_design_sweep(_gate_dev(policy), addrs, writes,
                                 capacity_frames=[4],
                                 is_lru=[policy == "lru"], outstanding=8)
        got = np.column_stack([out["latency_ticks"][0],
                               out["hit_flags"][0], out["evict_flags"][0]])
        np.testing.assert_array_equal(got, rows[0][:, :3])
    else:
        res, lat = MultiHostReplay(_gate_mounts(policy), outstanding=8,
                                   metrics=MetricsSpec()).run_recorded(tuples)
        for h in range(hosts):
            np.testing.assert_array_equal(lat[h], rows[h][:, 0])
        media = res.metrics.to_jsonable()["media"]
        for m, d in zip(media, devs):
            want = media_counters_of(d)
            assert {k: m[k] for k in want} == want


@pytest.mark.parametrize("d", [1, 4, 8, 256, 4096, 6])
def test_static_divisor_matches_floor_division(d):
    """The step's shift/mask division equals int64 floor division and
    modulo, negative dividends and non-powers of two included."""
    import jax.numpy as jnp
    from jax import enable_x64

    from repro.core.replay.stack import _floordiv, _mod

    x = np.array([-(1 << 62), -4097, -4096, -1, 0, 1, 7, 4095, 4096,
                  (1 << 62) - 1], np.int64)
    with enable_x64(True):
        q = np.asarray(_floordiv(jnp.asarray(x), d))
        r = np.asarray(_mod(jnp.asarray(x), d))
    np.testing.assert_array_equal(q, x // d)
    np.testing.assert_array_equal(r, x % d)


def test_multihost_pmem_pool_exact():
    """PMEM pools ride the same stacked-state path (open-row state is a
    per-device lane)."""
    def views():
        fab = Fabric.build("single_switch", num_hosts=2, num_devices=2)
        pool = MemoryPool(fab, {"d0": _mk("pmem"), "d1": _mk("pmem")})
        return pool.views(["h0", "h1"])

    traces = [_trace(99, n=600), _trace(100, n=500)]
    py = MultiHostDriver(views()).run(traces)
    rp = MultiHostReplay(views()).run(traces)
    _assert_multi_equal(py, rp)


def test_multihost_refusals_name_python_lane():
    # unsupported policy: the lane ladder names the fallback engine
    targets, _ = _cached_mounts(policy="2q")
    with pytest.raises(ReplayUnsupported, match="engine='python'"):
        MultiHostReplay(targets).run([_trace(101, n=64), _trace(102, n=64)])
    # heterogeneous cached configs must refuse, not silently average
    fab = Fabric.build("two_level", num_hosts=2, num_devices=2, num_leaves=2)
    a = fab.mount("h0", "d0", _mk("cxl-ssd-cache"))
    b = fab.mount("h1", "d1", make_device(
        "cxl-ssd-cache", cache_cfg=DRAMCacheConfig(
            capacity_bytes=8 * 4096, mshr_entries=4, writeback_buffer=2)))
    with pytest.raises(ReplayUnsupported, match="identically configured"):
        MultiHostReplay([a, b]).run([_trace(103, n=64), _trace(104, n=64)])


def test_host_count_sweep_cached_targets():
    from repro.core.replay.sweep import host_count_sweep

    traces = [_trace(105 + h, n=250) for h in range(4)]
    lanes = host_count_sweep(_cached_pool(), traces, [1, 2, 4])
    for h, lane in zip([1, 2, 4], lanes):
        py = MultiHostDriver(_cached_pool(h)).run(traces[:h])
        assert py.elapsed_ticks == lane.elapsed_ticks
        for a, b in zip(py.per_host, lane.per_host[:h]):
            _assert_equal(a, b)


if HAVE_HYPOTHESIS:
    GC_PAGES = st.lists(st.integers(0, 23), min_size=256, max_size=256)

    @settings(max_examples=6, deadline=None)
    @given(pages=GC_PAGES, writes=WRITES, offs=OFFSETS)
    def test_property_gc_scan_matches_python(pages, writes, offs):
        """Random GC-pressure traces (small over-provisioning, write-heavy):
        the fused GC is tick-exact against the python FTL — or BOTH sides
        fail (python raises out-of-space, the scan refuses); the scan never
        silently diverges."""
        trace = [(p * 4096 + o * 64, 64, w or i % 2 == 0)
                 for i, (p, o, w) in enumerate(zip(pages, offs, writes))]
        dev = _gc_device(cap_pages=96)
        try:
            py = TraceDriver(dev, outstanding=4).run(trace)
        except RuntimeError:
            with pytest.raises(ReplayUnsupported):
                ReplayEngine(_gc_device(cap_pages=96),
                             outstanding=4).run(trace)
            return
        rp = ReplayEngine(_gc_device(cap_pages=96), outstanding=4).run(trace)
        _assert_equal(py, rp)
        assert rp.gc_runs == dev.hil.ftl.stats["gc_runs"]


# ------------------------- associative transport primitive (satellite)
def _busy_fold(arr, svc, act, init):
    f, out = init, []
    for a, s, m in zip(arr, svc, act):
        if m:
            f = max(int(a), f) + int(s)
        out.append(f)
    return np.asarray(out, np.int64)


def _port_fold(arr, svc, ports, num_ports, init):
    f = [init] * num_ports
    out = []
    for a, s, p in zip(arr, svc, ports):
        f[p] = max(int(a), f[p]) + int(s)
        out.append(f[p])
    return np.asarray(out, np.int64)


def _random_transport_case(seed, n=257):
    """Random arrival/service sequences, including QoS-weighted service
    shapes: the weighted virtual-finish-time update ``vft = max(arr, vft)
    + pace`` is exactly this fold with per-access paces."""
    rng = np.random.default_rng(seed)
    arr = np.sort(rng.integers(0, 50_000, n)) - 10_000   # negatives too
    rng.shuffle(arr[: n // 4])                           # local disorder
    weights = rng.choice([1, 2, 3, 7], n)                # QoS weight mix
    svc = rng.integers(0, 900, n) * weights              # weighted paces
    act = rng.random(n) < 0.8
    ports = rng.integers(0, 5, n)                        # ECMP route choice
    return arr.astype(np.int64), svc.astype(np.int64), act, ports


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_assoc_busy_until_matches_sequential_fold(seed):
    from jax import enable_x64

    arr, svc, act, _ = _random_transport_case(seed)
    with enable_x64(True):
        got = np.asarray(busy_until(arr, svc, active=act, init=0))
        ungated = np.asarray(busy_until(arr, svc))
    assert (got == _busy_fold(arr, svc, act, 0)).all()
    # default init never binds: identical to a fold seeded below min(arr)
    ref = _busy_fold(arr, svc, np.ones_like(act), int(arr.min()) - 1)
    assert (ungated == ref).all()



@pytest.mark.parametrize("rows", [2, 2.5])
def test_assoc_scan_rows_equal_one_scan(rows):
    """Row-wise ``assoc_scan`` (the form the TPU compiles quickly) equals
    one trace-long associative scan: forward on the affine-max pair,
    padded or not, and reverse on a commutative min."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    from repro.core.replay.assoc import SCAN_ROW, _affine_max, _neg, assoc_scan

    arr, svc, act, _ = _random_transport_case(7, n=int(rows * SCAN_ROW))
    with enable_x64(True):
        neg = _neg(jnp.int64)
        a = jnp.where(act, svc, 0)
        b = jnp.where(act, arr + svc, neg)
        got = assoc_scan(_affine_max, (a, b), (0, neg))
        want = jax.lax.associative_scan(_affine_max, (a, b))
        big = jnp.iinfo(jnp.int64).max
        rmin = assoc_scan(jnp.minimum, jnp.asarray(arr), big, reverse=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(rmin),
                                  np.minimum.accumulate(arr[::-1])[::-1])


def test_assoc_busy_until_longer_than_a_row_matches_fold():
    from jax import enable_x64

    from repro.core.replay.assoc import SCAN_ROW

    arr, svc, act, _ = _random_transport_case(5, n=2 * SCAN_ROW + 3)
    with enable_x64(True):
        got = np.asarray(busy_until(arr, svc, active=act, init=0))
    assert (got == _busy_fold(arr, svc, act, 0)).all()

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_assoc_port_busy_until_matches_sequential_fold(seed):
    """ECMP route-choice case: each access occupies one of P interleaved
    port chains; the one-hot affine-max scan must equal the per-port
    fold."""
    from jax import enable_x64

    arr, svc, _, ports = _random_transport_case(seed)
    with enable_x64(True):
        got = np.asarray(port_busy_until(arr, svc, ports, 5, init=0))
    ref = _port_fold(arr, svc, ports, 5, 0)
    assert (got == ref).all()


def test_fill_latency_assoc_matches_kernel_and_ref():
    """The shared associative formulation reproduces the Pallas kernel's
    in-pass latency chain bit-for-bit (and hence the ref twin)."""
    from repro.kernels.cache_sim import cache_sim_fused, fill_latency_assoc
    from repro.kernels.ref import cache_sim_fused_ref

    rng = np.random.default_rng(42)
    pages = rng.integers(0, 256, 4000).astype(np.int32)
    writes = rng.random(4000) < 0.4
    kw = dict(num_sets=16, ways=4, policy="lru", outstanding=4, issue_ns=3,
              hit_ns=50, miss_ns=5213, miss_occ_ns=213, wb_ns=87)
    h, e, lat, arr = cache_sim_fused(pages, writes, **kw)
    lat_assoc = fill_latency_assoc(np.asarray(h), np.asarray(e),
                                   np.asarray(arr), hit_ns=kw["hit_ns"],
                                   miss_ns=kw["miss_ns"],
                                   miss_occ_ns=kw["miss_occ_ns"],
                                   wb_ns=kw["wb_ns"])
    np.testing.assert_array_equal(np.asarray(lat_assoc), np.asarray(lat))
    _, _, lat_ref = cache_sim_fused_ref(pages, writes, **kw)
    np.testing.assert_array_equal(np.asarray(lat_assoc), np.asarray(lat_ref))


# ------------------------------------------------- QoS + ECMP (tentpole)
def _qos_views(nh=3, weights=None):
    fab = Fabric.build("single_switch", num_hosts=nh, num_devices=1,
                       qos_weights=weights or {"h0": 3.0, "h1": 1.0,
                                               "h2": 2.0})
    pool = MemoryPool(fab, {"d0": DRAMDevice()})
    return pool.views([f"h{i}" for i in range(nh)])


def _ecmp_views(qos=False):
    fab = Fabric.build("spine_leaf", num_hosts=2, num_devices=2,
                       num_leaves=2, num_spines=3, ecmp=True,
                       qos_weights={"h0": 3.0, "h1": 1.0} if qos else None)
    pool = MemoryPool(fab, {"d0": DRAMDevice(), "d1": DRAMDevice()})
    return pool.views(["h0", "h1"])


def _assert_multi_equal(py, rp):
    assert py.elapsed_ticks == rp.elapsed_ticks
    for a, b in zip(py.per_host, rp.per_host):
        _assert_equal(a, b)


def test_multihost_qos_exact():
    traces = [_trace(60 + h, n=900) for h in range(3)]
    py = MultiHostDriver(_qos_views()).run(traces)
    rp = MultiHostReplay(_qos_views()).run(traces)
    _assert_multi_equal(py, rp)


def test_multihost_ecmp_exact():
    traces = [_trace(64 + h, n=900) for h in range(2)]
    py = MultiHostDriver(_ecmp_views()).run(traces)
    rp = MultiHostReplay(_ecmp_views()).run(traces)
    _assert_multi_equal(py, rp)


def test_multihost_qos_plus_ecmp_exact():
    traces = [_trace(66 + h, n=900) for h in range(2)]
    py = MultiHostDriver(_ecmp_views(qos=True)).run(traces)
    rp = MultiHostReplay(_ecmp_views(qos=True)).run(traces)
    _assert_multi_equal(py, rp)


def test_singlehost_ecmp_replay_engine_exact():
    def mk():
        fab = Fabric.build("spine_leaf", num_hosts=1, num_devices=1,
                           num_leaves=2, num_spines=3, ecmp=True)
        return fab.mount("h0", "d0", DRAMDevice())

    trace = _trace(68, n=900)
    py = TraceDriver(mk(), outstanding=8).run(trace)
    rp = ReplayEngine(mk(), outstanding=8).run(trace)
    _assert_equal(py, rp)


def test_singlehost_on_qos_fabric_exact_without_mirror():
    """A lone origin's QoS floor provably never binds, so ReplayEngine
    needs no QoS state at all — but the outputs must still agree with the
    interpreted path, which *does* run the arbitration arithmetic."""
    def mk():
        fab = Fabric.build("single_switch", num_hosts=2, num_devices=1,
                           qos_weights={"h0": 7.0, "h1": 1.0})
        return fab.mount("h0", "d0", DRAMDevice())

    trace = _trace(69, n=900)
    py = TraceDriver(mk(), outstanding=8).run(trace)
    rp = ReplayEngine(mk(), outstanding=8).run(trace)
    _assert_equal(py, rp)


def test_qos_duplicate_host_names_rejected():
    views = _qos_views()
    with pytest.raises(ReplayUnsupported):
        MultiHostReplay([views[0], views[0]]).run(
            [_trace(70, n=64), _trace(71, n=64)])


def test_qos_negative_start_tick_rejected():
    with pytest.raises(ReplayUnsupported):
        MultiHostReplay(_qos_views()).run(
            [_trace(72, n=64) for _ in range(3)], start_tick=-5)


if HAVE_HYPOTHESIS:
    WEIGHT = st.sampled_from([0.5, 1.0, 2.0, 3.0, 7.0])

    @settings(max_examples=8, deadline=None)
    @given(pages=PAGES, writes=WRITES, w0=WEIGHT, w1=WEIGHT, w2=WEIGHT)
    def test_property_qos_scan_matches_python(pages, writes, w0, w1, w2):
        """The tentpole acceptance criterion, property-tested: arbitrary
        weight mixes stay tick-identical between the interpreted driver
        and the fused scan (including the all-equal FCFS degeneration)."""
        weights = {"h0": w0, "h1": w1, "h2": w2}
        traces = [[(p * 4096, 64, w) for p, w in zip(pages, writes)]
                  for _ in range(3)]
        py = MultiHostDriver(_qos_views(weights=weights)).run(traces)
        rp = MultiHostReplay(_qos_views(weights=weights)).run(traces)
        _assert_multi_equal(py, rp)

    @settings(max_examples=6, deadline=None)
    @given(pages=PAGES, writes=WRITES)
    def test_property_ecmp_scan_matches_python(pages, writes):
        traces = [[(p * 4096 + o * 64, 64, w)
                   for p, o, w in zip(pages, range(256), writes)]
                  for _ in range(2)]
        py = MultiHostDriver(_ecmp_views(qos=True)).run(traces)
        rp = MultiHostReplay(_ecmp_views(qos=True)).run(traces)
        _assert_multi_equal(py, rp)

    ARRIVALS = st.lists(st.integers(-5_000, 100_000), min_size=64,
                        max_size=64)
    SERVICES = st.lists(st.integers(0, 3_000), min_size=64, max_size=64)
    GATES = st.lists(st.booleans(), min_size=64, max_size=64)
    PORTS = st.lists(st.integers(0, 3), min_size=64, max_size=64)

    @settings(max_examples=30, deadline=None)
    @given(arr=ARRIVALS, svc=SERVICES, act=GATES, ports=PORTS,
           weights=st.lists(st.sampled_from([1, 2, 3, 7]), min_size=64,
                            max_size=64))
    def test_property_assoc_transport_matches_fold(arr, svc, act, ports,
                                                   weights):
        """Satellite property: the associative max-plus transport equals
        the sequential busy-until fold for arbitrary arrival/service
        sequences — including QoS-weighted paces (service = occ * W/w, the
        virtual-finish-time update) and ECMP route choices (per-access
        port selection)."""
        from jax import enable_x64

        arr = np.asarray(arr, np.int64)
        paced = np.asarray(svc, np.int64) * np.asarray(weights, np.int64)
        act = np.asarray(act)
        ports = np.asarray(ports)
        with enable_x64(True):
            gated = np.asarray(busy_until(arr, paced, active=act, init=0))
            perport = np.asarray(port_busy_until(arr, paced, ports, 4,
                                                 init=0))
        assert (gated == _busy_fold(arr, paced, act, 0)).all()
        assert (perport == _port_fold(arr, paced, ports, 4, 0)).all()

    @settings(max_examples=6, deadline=None)
    @given(pages=PAGES, writes=WRITES, offs=OFFSETS,
           name=st.sampled_from(["dram", "pmem"]))
    def test_property_assoc_matches_python_or_refuses(pages, writes, offs,
                                                      name):
        """The assoc lane either reproduces the interpreted driver
        tick-for-tick or raises — silence is never an option."""
        trace = [(p * 4096 + o * 64, 64, w)
                 for p, o, w in zip(pages, offs, writes)]
        py = TraceDriver(_mk(name)).run(trace)
        try:
            rp = AssocReplayEngine(_mk(name)).run(trace)
        except ReplayUnsupported:
            return
        _assert_equal(py, rp)
