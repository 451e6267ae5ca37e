"""Deterministic fault injection and graceful degradation (PR 7).

Covers the four fault classes end to end: the :class:`FaultPlan` schedule
is a pure function of ``(seed, config)`` (hash twins agree across the
scalar / numpy / traced-jnp implementations), routing degrades gracefully
under down windows (ECMP exclusion, failover reroutes, typed
:class:`DeviceUnreachable` when a device is isolated), poison rides the
flit encode/decode roundtrip as status, and — the tick-identity contract —
the fused scan replays fault-injected traces access-for-access equal to
the interpreted drivers, or refuses with :class:`ReplayUnsupported`.
"""

import numpy as np
import pytest
from jax import enable_x64

from golden.scenarios import ServiceTap
from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.cxl.flit import CXLCommand, CXLFlit, decode_flit, encode_flit
from repro.core.devices import make_device
from repro.core.fabric import Fabric, MemoryPool
from repro.core.faults import (DeviceUnreachable, FaultConfig, FaultPlan,
                               erase_fails_jnp, fault_hash, fault_hash_np,
                               install, nand_read_retries_jnp)
from repro.core.replay import (AssocReplayEngine, MultiHostReplay,
                               ReplayEngine, ReplayUnsupported)
from repro.core.replay.metrics import MetricsSpec
from repro.core.workloads.driver import MultiHostDriver, TraceDriver

CACHE_KW = dict(capacity_bytes=16 * 4096, mshr_entries=4, writeback_buffer=2)
DEVICES = ["dram", "cxl-dram", "pmem", "cxl-ssd", "cxl-ssd-cache"]
OUT = 8


def _mk_device(name):
    if name == "cxl-ssd-cache":
        return make_device(name,
                           cache_cfg=DRAMCacheConfig(policy="lru", **CACHE_KW))
    return make_device(name)


def _mount(name, topo="spine_leaf", ecmp=False, qos=None):
    kw = dict(num_hosts=2, num_devices=2)
    if topo == "spine_leaf":
        kw.update(num_leaves=2, num_spines=2)
    if qos:
        kw["qos_weights"] = qos
    fab = Fabric.build(topo, ecmp=ecmp, **kw)
    return fab.mount("h0", "d0", _mk_device(name))


def _trace(seed, n=160, pages=24, write_frac=0.3):
    rng = np.random.default_rng(seed)
    addrs = rng.integers(0, pages, n) * 4096 + rng.integers(0, 64, n) * 64
    writes = rng.random(n) < write_frac
    return [(int(a), 64, bool(w)) for a, w in zip(addrs, writes)]


def _parity(mk, cfg, seed=7, trace=None, counters_only=False):
    """python tap latencies == fused scan latencies, and (unless QoS's
    pre-existing single-host throttle-count divergence is in play) the
    full metrics bundles — fault counters included — byte-equal."""
    trace = trace or _trace(11)
    t1 = mk()
    install(FaultPlan(cfg, seed=seed), [t1])
    tap = ServiceTap(t1)
    TraceDriver(tap, outstanding=OUT).run(trace)
    t2 = mk()
    install(FaultPlan(cfg, seed=seed), [t2])
    res = ReplayEngine(t2, outstanding=OUT, metrics=MetricsSpec()).run(trace)
    assert np.array_equal(np.asarray(tap.latencies),
                          np.asarray(res.latency_ticks))
    t3 = mk()
    install(FaultPlan(cfg, seed=seed), [t3])
    py = TraceDriver(t3, outstanding=OUT, engine="python",
                     metrics=MetricsSpec()).run(trace)
    jp, js = py.metrics.to_jsonable(), res.metrics.to_jsonable()
    if counters_only:
        assert jp["faults"] == js["faults"]
    else:
        assert jp == js
    return res, js


# ------------------------------------------------------------ hash twins
def test_fault_hash_twins_agree():
    ords = np.arange(512, dtype=np.int64)
    for salt in (0xA1A1, 0xC3C3, 0xE5E5):
        scalar = np.asarray([fault_hash(9, salt, 5, int(o)) for o in ords],
                            np.uint64)
        assert np.array_equal(scalar, fault_hash_np(9, salt, 5, ords))


def test_nand_jnp_twins_agree():
    plan = FaultPlan(FaultConfig(nand_read_retry_rate=0.35,
                                 nand_read_retry_max=2,
                                 erase_fail_rate=0.4), seed=3)
    statics = plan.nand_statics()
    with enable_x64(True):
        import jax.numpy as jnp
        for seq in range(200):
            assert plan.nand_read_retries(seq) == int(
                nand_read_retries_jnp(statics, jnp.int64(seq)))
            assert plan.erase_fails(seq) == bool(
                erase_fails_jnp(statics, jnp.int64(seq)))


def test_link_and_poison_vector_twins_agree():
    plan = FaultPlan(FaultConfig(link_retry_rate=0.3, link_retry_max=3,
                                 poison_rate=0.2), seed=5)
    ords = np.arange(400, dtype=np.int64)
    scalar = [plan.link_retries(("s0", "sp1"), int(o)) for o in ords]
    assert np.array_equal(np.asarray(scalar),
                          plan.link_retries_np(("s0", "sp1"), ords))
    writes = (ords % 3) == 0
    scalar_p = [plan.poisoned(0, int(o), bool(w))
                for o, w in zip(ords, writes)]
    assert np.array_equal(np.asarray(scalar_p),
                          plan.poisoned_np(0, ords, writes))


def test_plan_is_pure_function_of_seed_and_config():
    cfg = FaultConfig(link_retry_rate=0.25, nand_read_retry_rate=0.3,
                      poison_rate=0.1)
    a, b = FaultPlan(cfg, seed=42), FaultPlan(cfg, seed=42)
    ords = np.arange(300, dtype=np.int64)
    assert np.array_equal(a.link_retries_np(("u", "v"), ords),
                          b.link_retries_np(("u", "v"), ords))
    assert np.array_equal(a.poisoned_np(1, ords, ords % 2 == 0),
                          b.poisoned_np(1, ords, ords % 2 == 0))
    assert [a.nand_read_retries(s) for s in range(100)] \
        == [b.nand_read_retries(s) for s in range(100)]
    other = FaultPlan(cfg, seed=43)
    assert not np.array_equal(a.link_retries_np(("u", "v"), ords),
                              other.link_retries_np(("u", "v"), ords))


# ----------------------------------------------------- down-window routing
def test_down_window_is_directed_both_ways_and_bounded():
    plan = FaultPlan(FaultConfig(down_links=(("a", "b", 10, 20),)), seed=0)
    assert plan.down_links_at(9) == frozenset()
    assert plan.down_links_at(10) == frozenset({("a", "b"), ("b", "a")})
    assert plan.down_links_at(19) == frozenset({("a", "b"), ("b", "a")})
    assert plan.down_links_at(20) == frozenset()


def test_routing_select_degrades_then_raises_spine_leaf():
    fab = Fabric.build("spine_leaf", num_hosts=2, num_devices=2,
                       num_leaves=2, num_spines=2, ecmp=True)
    rt = fab.routing
    # two equal-cost spine paths h0 -> d0; one spine down -> the other
    one = frozenset({("s0", "sp0"), ("sp0", "s0")})
    paths = rt.paths("h0", "d0", down=one)
    assert len(paths) == 1 and "sp1" in paths[0]
    assert "sp1" in rt.select("h0", "d0", 0, down=one)
    # both spines down from the leaf -> no route at all
    both = frozenset({("s0", "sp0"), ("s0", "sp1")})
    with pytest.raises(DeviceUnreachable):
        rt.select("h0", "d0", 0, down=both)


def test_routing_failover_then_raises_mesh():
    fab = Fabric.build("mesh", num_hosts=2, num_devices=2)
    rt = fab.routing
    nominal = rt.path("h0", "d0")
    sw = [n for n in nominal if n.startswith("s")]
    # cut the first switch-to-switch hop of the nominal path: a longer
    # recomputed route must take over
    cut = frozenset({(sw[0], sw[1]), (sw[1], sw[0])})
    alt = rt.select("h0", "d0", 0, down=cut)
    assert alt != nominal and alt[0] == "h0" and alt[-1] == "d0"
    # sever every edge out of h0's switch -> isolated
    edges = {(u, v) for (u, v) in fab.ports if u == sw[0] or v == sw[0]}
    with pytest.raises(DeviceUnreachable):
        rt.select("h0", "d0", 0, down=frozenset(edges))


def test_isolated_device_raises_through_service():
    fab = Fabric.build("direct", num_pairs=2)
    tgt = fab.mount("h0", "d0", _mk_device("dram"))
    install(FaultPlan(FaultConfig(down_links=(("h0", "d0", 0, 1000),)),
                      seed=1), [tgt])
    with pytest.raises(DeviceUnreachable):
        TraceDriver(tgt, outstanding=OUT).run(_trace(3, n=8))


# --------------------------------------------------- poison flit roundtrip
def test_poison_flit_roundtrip_property():
    rng = np.random.default_rng(0)
    for _ in range(200):
        flit = CXLFlit(opcode=CXLCommand.S2MDRS,
                       addr=int(rng.integers(0, 1 << 40)) * 64,
                       tag=int(rng.integers(0, 1 << 16)),
                       poison=bool(rng.integers(0, 2)),
                       dirty_evict=bool(rng.integers(0, 2)))
        back = decode_flit(encode_flit(flit))
        assert back.poison == flit.poison
        assert back.dirty_evict == flit.dirty_evict
        assert (back.addr, back.tag) == (flit.addr, flit.tag)


def test_decode_rejects_reserved_flag_bits():
    raw = bytearray(encode_flit(CXLFlit(opcode=CXLCommand.S2MDRS,
                                        addr=0, tag=1)))
    # flags byte is at offset 15 (<BBBHQH is unpadded: 1+1+1+2+8+2)
    raw[15] |= 0b100
    with pytest.raises(ValueError, match="reserved flag bits"):
        decode_flit(bytes(raw))
    raw[15] = 0b01      # poison alone still decodes
    assert decode_flit(bytes(raw)).poison


# ------------------------------------------------- python == scan parity
@pytest.mark.parametrize("name", DEVICES)
def test_parity_random_plan_per_device(name):
    """Every paper device under a randomized (but seeded) mixed fault plan
    on an ECMP spine-leaf mount: per-access latencies and fault counters
    must be tick/byte-identical between the interpreted driver and the
    fused scan."""
    rng = np.random.default_rng(sum(ord(c) for c in name))
    kw = dict(link_retry_rate=float(rng.uniform(0.05, 0.4)),
              link_retry_max=int(rng.integers(1, 4)),
              poison_rate=float(rng.uniform(0.0, 0.2)))
    if rng.random() < 0.5:
        first = int(rng.integers(0, 60))
        kw["down_links"] = (("s0", "sp0", first,
                             first + int(rng.integers(20, 80))),)
    if name in ("cxl-ssd", "cxl-ssd-cache"):
        kw["nand_read_retry_rate"] = float(rng.uniform(0.1, 0.4))
    _parity(lambda: _mount(name, ecmp=True), FaultConfig(**kw),
            seed=int(rng.integers(0, 1 << 16)))


def test_parity_failover_reroute_mesh():
    res, js = _parity(lambda: _mount("cxl-dram", topo="mesh"),
                      FaultConfig(down_links=(("s0_0", "s0_1", 10, 70),)))
    assert js["faults"]["failovers"] > 0


def test_parity_qos_latencies_and_fault_counters():
    # full-bundle equality is excluded on single-host QoS mounts: the
    # interpreted qos_throttle_events counter diverges there even without
    # faults (pre-existing, unpinned); latencies + fault counters must agree
    _parity(lambda: _mount("dram", ecmp=True,
                           qos={"h0": 3.0, "h1": 1.0}),
            FaultConfig(link_retry_rate=0.2,
                        down_links=(("s0", "sp1", 30, 100),)),
            counters_only=True)


def test_poison_surfaces_as_status_not_latency():
    cfg = FaultConfig(poison_rate=0.25)
    res, js = _parity(lambda: _mount("pmem"), cfg, seed=9)
    plan = FaultPlan(cfg, seed=9)
    trace = _trace(11)
    writes = np.asarray([w for _, _, w in trace])
    expect = plan.poisoned_np(0, np.arange(len(trace), dtype=np.int64),
                              writes)
    assert np.array_equal(res.poison_flags, expect)
    assert js["faults"]["poisoned_reads"] == int(expect.sum())
    # clean twin: identical latencies — poison is status, never latency
    t_clean = _mount("pmem")
    clean = ReplayEngine(t_clean, outstanding=OUT).run(trace)
    t_f = _mount("pmem")
    install(plan, [t_f])
    faulted = ReplayEngine(t_f, outstanding=OUT).run(trace)
    assert np.array_equal(clean.latency_ticks, faulted.latency_ticks)


def _mh_targets(plan_cfg=None, seed=5, qos=False, ecmp=False):
    kw = dict(num_hosts=2, num_devices=2, num_leaves=2, num_spines=2)
    if qos:
        kw["qos_weights"] = {"h0": 2.0, "h1": 1.0}
    fab = Fabric.build("spine_leaf", ecmp=ecmp, **kw)
    tgts = [fab.mount(f"h{i}", f"d{i}", _mk_device("cxl-ssd-cache"))
            for i in range(2)]
    if plan_cfg is not None:
        install(FaultPlan(plan_cfg, seed=seed), tgts)
    return tgts


def test_parity_multihost_nand_qos_ecmp():
    cfg = FaultConfig(nand_read_retry_rate=0.35)
    traces = [_trace(21, n=200, write_frac=0.5), _trace(22, n=200,
                                                        write_frac=0.5)]
    py = MultiHostDriver(_mh_targets(cfg), outstanding=OUT,
                         metrics=MetricsSpec()).run(traces)
    eng = MultiHostReplay(_mh_targets(cfg), outstanding=OUT,
                          metrics=MetricsSpec())
    rp, lat = eng.run_recorded(traces)
    taps = [ServiceTap(t) for t in _mh_targets(cfg)]
    MultiHostDriver(taps, outstanding=OUT).run(traces)
    for tap, l in zip(taps, lat):
        assert np.array_equal(np.asarray(tap.latencies), np.asarray(l))
    jp, js = py.metrics.to_jsonable(), rp.metrics.to_jsonable()
    assert jp == js
    assert js["faults"]["nand_read_retries"] > 0
    # QoS + ECMP multihost mounts fuse too (NAND-only plan)
    py2 = MultiHostDriver(_mh_targets(cfg, qos=True, ecmp=True),
                          outstanding=OUT, metrics=MetricsSpec()).run(traces)
    rp2 = MultiHostReplay(_mh_targets(cfg, qos=True, ecmp=True),
                          outstanding=OUT, metrics=MetricsSpec()).run(traces)
    assert py2.metrics.to_jsonable() == rp2.metrics.to_jsonable()
    assert py2.elapsed_ticks == rp2.elapsed_ticks


# ---------------------------------------- multi-host transport parity
def _mh_parity(cfg, seed=5, qos=False, ecmp=False, chunk=None):
    """Fused multi-host replay under transport faults: per-host latency
    streams and the full metrics bundle (fault counters included) must be
    tick/byte-identical to the interpreted MultiHostDriver."""
    traces = [_trace(21, n=120, write_frac=0.5),
              _trace(22, n=120, write_frac=0.5)]
    py = MultiHostDriver(_mh_targets(cfg, seed, qos, ecmp),
                         outstanding=OUT, metrics=MetricsSpec()).run(traces)
    eng = MultiHostReplay(_mh_targets(cfg, seed, qos, ecmp),
                          outstanding=OUT, metrics=MetricsSpec())
    rp, lat = eng.run_recorded(traces, chunk_size=chunk)
    taps = [ServiceTap(t) for t in _mh_targets(cfg, seed, qos, ecmp)]
    MultiHostDriver(taps, outstanding=OUT).run(traces)
    for tap, l in zip(taps, lat):
        assert np.array_equal(np.asarray(tap.latencies), np.asarray(l))
    js = rp.metrics.to_jsonable()
    assert py.metrics.to_jsonable() == js
    return rp, js


def test_parity_multihost_link_retries():
    rp, js = _mh_parity(FaultConfig(link_retry_rate=0.3))
    assert js["faults"]["link_retries"] > 0


def test_parity_multihost_port_down_ecmp_and_failover():
    rp, js = _mh_parity(FaultConfig(down_links=(("s0", "sp0", 20, 90),)),
                        ecmp=True)
    assert js["faults"]["degraded_accesses"] > 0
    # non-ECMP spine-leaf: the same window forces failover reroutes
    rp2, js2 = _mh_parity(FaultConfig(down_links=(("s0", "sp0", 20, 90),)))
    assert js2["faults"]["failovers"] > 0


def test_parity_multihost_poison_status():
    rp, js = _mh_parity(FaultConfig(poison_rate=0.2))
    assert js["faults"]["poisoned_reads"] > 0


def test_parity_multihost_mixed_qos_ecmp():
    rp, js = _mh_parity(FaultConfig(link_retry_rate=0.2,
                                    down_links=(("s0", "sp1", 30, 100),),
                                    poison_rate=0.1),
                        qos=True, ecmp=True)
    for k in ("link_retries", "degraded_accesses", "poisoned_reads"):
        assert js["faults"][k] > 0


def test_multihost_fault_flags_exposed_for_availability():
    cfg = FaultConfig(down_links=(("s0", "sp0", 20, 90),))
    traces = [_trace(21, n=120), _trace(22, n=120)]
    eng = MultiHostReplay(_mh_targets(cfg, ecmp=True), outstanding=OUT)
    eng.run(traces)
    deg, fo = eng.fault_flags
    assert deg.shape == (2, 120) and fo.shape == (2, 120)
    assert deg[:, :20].sum() == 0 and deg[:, 20:90].any()


def test_multihost_unreachable_raises_at_prepare():
    # both spines down for the whole run: no surviving route, typed error
    cfg = FaultConfig(down_links=(("s0", "sp0", 0, 1000),
                                  ("s0", "sp1", 0, 1000)))
    traces = [_trace(31, n=16), _trace(32, n=16)]
    with pytest.raises(DeviceUnreachable):
        MultiHostReplay(_mh_targets(cfg, ecmp=True)).run(traces)


# ------------------------------------------------------ typed refusals
def test_multihost_pool_refuses_transport_faults_naming_classes():
    from repro.core.devices import DRAMDevice
    fab = Fabric.build("two_level", num_hosts=2, num_devices=2,
                       num_leaves=2)
    pool = MemoryPool(fab, {"d0": DRAMDevice(), "d1": DRAMDevice()})
    views = pool.views(["h0", "h1"])
    # install() refuses pool views, so wire the plan onto the fabric the
    # way a mounted topology would carry it
    fab.fault_plan = FaultPlan(FaultConfig(link_retry_rate=0.3,
                                           down_links=(("s0", "l0", 0,
                                                        50),)), seed=2)
    traces = [_trace(31, n=16), _trace(32, n=16)]
    with pytest.raises(ReplayUnsupported,
                       match="link-retry, port-down.*pool address "
                             "interleaving.*engine='python'"):
        MultiHostReplay(views).run(traces)


def test_assoc_and_pallas_refusals_name_fault_class_and_lane():
    tgt = _mount("dram")
    install(FaultPlan(FaultConfig(link_retry_rate=0.3,
                                  poison_rate=0.1), seed=2), [tgt])
    with pytest.raises(ReplayUnsupported,
                       match="link-retry, poison.*engine='scan'"):
        AssocReplayEngine(tgt, outstanding=OUT).run(_trace(4, n=32))
    from repro.core.replay.pallas_engine import run_pallas
    dev = _mk_device("cxl-ssd-cache")
    install(FaultPlan(FaultConfig(nand_read_retry_rate=0.3), seed=2), [dev])
    addrs = np.asarray([a for a, _, _ in _trace(4, n=32)], np.int64)
    writes = np.asarray([w for _, _, w in _trace(4, n=32)], bool)
    with pytest.raises(ReplayUnsupported, match="NAND.*engine='scan'"):
        run_pallas(dev, addrs, writes)
    # an inert plan (all rates zero) constrains nothing
    t2 = _mount("dram")
    install(FaultPlan(FaultConfig(), seed=2), [t2])
    AssocReplayEngine(t2, outstanding=OUT).run(_trace(4, n=32))


def test_pool_views_refuse_fault_install():
    from repro.core.devices import DRAMDevice
    fab = Fabric.build("two_level", num_hosts=2, num_devices=2, num_leaves=2)
    pool = MemoryPool(fab, {"d0": DRAMDevice(), "d1": DRAMDevice()})
    views = pool.views(["h0", "h1"])
    with pytest.raises(TypeError):
        install(FaultPlan(FaultConfig(link_retry_rate=0.1), seed=0), views)


# ------------------------------------------------------- perfetto export
def test_perfetto_export_carries_fault_instants(tmp_path):
    import json

    from repro.obs import write_perfetto

    tgt = _mount("dram", ecmp=True)
    install(FaultPlan(FaultConfig(link_retry_rate=0.3,
                                  poison_rate=0.1), seed=4), [tgt])
    res = ReplayEngine(tgt, outstanding=OUT,
                       metrics=MetricsSpec()).run(_trace(11))
    doc = json.load(open(write_perfetto(res, str(tmp_path / "t.json"))))
    events = doc["traceEvents"]
    procs = {e["args"]["name"] for e in events
             if e["name"] == "process_name"}
    assert "faults" in procs
    instants = [e for e in events if e["ph"] == "i"]
    assert any(e["name"].startswith("link_retries=") for e in instants)
    assert any(e["name"].startswith("poisoned_reads=") for e in instants)
    summary = [e for e in events if e["name"] == "fault_counters"]
    assert summary and summary[0]["args"]["link_retries"] > 0
    # fault-free runs export no faults process (schema unchanged)
    clean = ReplayEngine(_mount("dram"), outstanding=OUT,
                         metrics=MetricsSpec()).run(_trace(11))
    doc2 = json.load(open(write_perfetto(clean, str(tmp_path / "c.json"))))
    procs2 = {e["args"]["name"] for e in doc2["traceEvents"]
              if e["name"] == "process_name"}
    assert "faults" not in procs2


def test_perfetto_export_renders_down_window_spans(tmp_path):
    import json

    from repro.core.replay.metrics import down_window_spans
    from repro.obs import write_perfetto

    cfg = FaultConfig(down_links=(("s0", "sp0", 30, 90),))
    tgt = _mount("dram", ecmp=True)
    plan = install(FaultPlan(cfg, seed=4), [tgt])
    res = ReplayEngine(tgt, outstanding=OUT,
                       metrics=MetricsSpec()).run(_trace(11))
    iss = np.cumsum(np.full(160, 100, np.int64))
    spans = down_window_spans(plan, [iss], hosts=["h0"])
    assert spans and spans[0]["link"] == "s0<->sp0"
    assert spans[0]["start_tick"] == int(iss[30])
    doc = json.load(open(write_perfetto(res, str(tmp_path / "d.json"),
                                        down_windows=spans)))
    xs = [e for e in doc["traceEvents"]
          if e["ph"] == "X" and e["name"].startswith("down ")]
    assert len(xs) == len(spans)
    assert xs[0]["args"]["link"] == "s0<->sp0"
    assert xs[0]["dur"] > 0
    # spans land in the faults process group
    pids = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
            if e["name"] == "process_name"}
    assert pids[xs[0]["pid"]] == "faults"


# --------------------------------------------- property suite (hypothesis)
# Random seeded FaultPlans; skips cleanly when the dev extra is absent.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    PLANS = st.fixed_dictionaries({
        "link_retry_rate": st.floats(0.0, 0.5),
        "link_retry_max": st.integers(1, 4),
        "nand_read_retry_rate": st.floats(0.0, 0.5),
        "poison_rate": st.floats(0.0, 0.3),
    })

    @settings(max_examples=8, deadline=None)
    @given(kw=PLANS, seed=st.integers(0, 2**31 - 1),
           device=st.sampled_from(DEVICES))
    def test_random_fault_plans_replay_tick_exact(kw, seed, device):
        _parity(lambda: _mount(device, ecmp=True), FaultConfig(**kw),
                seed=seed, trace=_trace(13))

    MH_PLANS = st.fixed_dictionaries({
        "link_retry_rate": st.floats(0.0, 0.4),
        "link_retry_max": st.integers(1, 3),
        "poison_rate": st.floats(0.0, 0.2),
    })

    @settings(max_examples=6, deadline=None)
    @given(kw=MH_PLANS, seed=st.integers(0, 2**31 - 1),
           qos=st.booleans(), ecmp=st.booleans(), down=st.booleans())
    def test_random_multihost_transport_plans_tick_exact(kw, seed, qos,
                                                         ecmp, down):
        """Fused multi-host transport faults across the QoS x ECMP grid on
        spine-leaf: tick/byte-identical to the interpreted driver for any
        seeded plan mix (down windows included)."""
        if down:
            kw = dict(kw, down_links=(("s0", "sp0", 20, 90),))
        _mh_parity(FaultConfig(**kw), seed=seed, qos=qos, ecmp=ecmp)
