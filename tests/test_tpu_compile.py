"""Compile the main replay path for a described TPU v5e (2x2) without one.

The TPU compiler is installed with JAX, so it can compile for a chip that
is described and not attached: these tests refuse a kernel or program the
chip would refuse (tiling, memory spaces, unsupported reductions, device
memory) at no chip time.  Nothing runs, so they say nothing about results
or speed.  The topology is described inside a fixture, never at import:
only the worker that runs this file may load the TPU library.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.devices import make_device
from repro.core.fabric import Fabric, MemoryPool
from repro.core.replay import (MetricsSpec, MultiHostReplay,
                               ShardedMultiHostReplay, build_stack)
from repro.core.replay.multihost import _compact_prepared
from repro.core.replay.spec import compact_pages
from repro.data import WorkloadSpec, host_trace_np, traces_np
from repro.kernels.cache_sim import cache_sim_fused

HBM_BYTES = 16 * 2**30          # one v5e chip
ZIPF = WorkloadSpec("zipfian", num_pages=65_536, zipf_s=0.99,
                    write_frac=0.3)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # entries compiled for a described chip cannot be read back here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), jnp.asarray(v).dtype,
                                       sharding=sharding), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("num_sets,ways,policy",
                         [(1, 4096, "lru"), (4096, 1, "direct")],
                         ids=["fully-assoc-lru", "direct-mapped"])
def test_cache_sim_fused_compiles_at_paper_geometry(one_chip, num_sets,
                                                    ways, policy):
    n = 1 << 20
    lowered = jax.jit(lambda p, w: cache_sim_fused(
        p, w, num_sets=num_sets, ways=ways, policy=policy,
        interpret=False)).lower(
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def _table1_stack(n, counters):
    """The Table I cached CXL-SSD's stack and a trace of ``n`` accesses,
    as ``ReplayEngine.run_arrays`` hands them to the device."""
    addrs, writes = host_trace_np(ZIPF, 0, 0, n)
    cfg, params = build_stack(
        make_device("cxl-ssd-cache"), size=64, outstanding=32,
        issue_overhead_ns=0.5, posted_writes=True, n_accesses=n,
        max_addr=int(addrs.max()), counters=counters)
    cfg, addrs = compact_pages(cfg, addrs)
    return cfg, params, addrs, writes


@pytest.fixture(scope="module")
def table1_scan(one_chip):
    """The single-host scan of the Table I cached CXL-SSD at
    ``tableI.zipf``'s shapes (32,768 accesses), compiled."""
    from repro.core.replay.engine import _run_stack

    n = 1 << 15
    with jax.enable_x64(True):
        cfg, params, _, _ = _table1_stack(n, counters=True)
        compiled = _run_stack.lower(
            cfg, _shapes(params, one_chip),
            jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
            1, MetricsSpec(), True, 64).compile()
    return cfg, compiled


@pytest.fixture(scope="module")
def table1_sweep(one_chip):
    """The design sweep at ``tableI.sweep64``'s shapes, compiled: 16,384
    accesses under 64 designs (32 capacities x LRU/FIFO), vmapped."""
    from repro.core.replay.sweep import _run_cache_lanes

    n, caps = 1 << 14, np.arange(1, 33, dtype=np.int64) * 128
    with jax.enable_x64(True):
        cfg, params, addrs, writes = _table1_stack(n, counters=False)
        params = {**params, "is_lru": np.repeat([True, False], 32),
                  "cap": np.tile(caps, 2)}
        compiled = _run_cache_lanes.lower(
            cfg, _shapes(params, one_chip),
            _shapes((addrs, writes), one_chip),
            frozenset({"is_lru", "cap"}), None).compile()
    return cfg, compiled


@pytest.fixture(scope="module")
def mld8_multi(one_chip):
    """The multi-host scan at ``mld8.zipf``'s shapes, compiled: eight hosts,
    each in its own 2 GiB LD of one Table I cached CXL-SSD behind one
    switch, 4,096 accesses a host, metrics with per-access streams."""
    from repro.core.replay.multihost import _run_multi

    hosts, n = 8, 4096
    fab = Fabric.build("single_switch", num_hosts=hosts, num_devices=1)
    pool = MemoryPool(fab, {"d0": make_device("cxl-ssd-cache")},
                      ld_bytes=2 * 2**30)
    eng = MultiHostReplay(pool.views([f"h{i}" for i in range(hosts)]),
                          metrics=MetricsSpec())
    addrs, writes = traces_np(ZIPF, 0, hosts, n)
    with jax.enable_x64(True):
        cfg, params, devs, a, w, lens, size = _compact_prepared(
            eng.prepare_arrays(addrs, writes))
        compiled = _run_multi.lower(
            cfg, *_shapes((params, devs, a, w, lens, np.int64(0)),
                          one_chip),
            1, MetricsSpec(), True, size).compile()
    return cfg, compiled


@pytest.fixture(scope="module")
def fleet_sharded(topo):
    """The sharded fleet runner on the described 2x2 mesh, compiled: eight
    cached CXL-SSD hosts on a two-pod ECMP fabric, two a chip, 2,048
    accesses a host, metrics with per-access streams."""
    from repro.core.replay.shard import _build_runner

    hosts, n = 8, 2048
    fab = Fabric.build("multi_pod", ecmp=True, num_pods=2,
                       hosts_per_pod=hosts // 2)
    mounts = [fab.mount(f"h{i}", f"d{i}", make_device("cxl-ssd-cache"))
              for i in range(hosts)]
    eng = ShardedMultiHostReplay(mounts, metrics=MetricsSpec())
    addrs, writes = traces_np(ZIPF, 0, hosts, n)
    with jax.enable_x64(True):
        cfg, params, _, a, w, lens, size = _compact_prepared(
            eng.prepare_arrays(addrs, writes))
        sh, rep = eng._shard_tensors(cfg, params, lens, a, w)
        run, mesh = _build_runner(cfg, tuple(topo.devices), 1,
                                  MetricsSpec(), True, size)
        compiled = run.lower(
            jax.ShapeDtypeStruct((), jnp.int64,
                                 sharding=NamedSharding(mesh, P())),
            _shapes(sh, NamedSharding(mesh, P("hosts"))),
            _shapes(rep, NamedSharding(mesh, P()))).compile()
    return cfg, compiled


def test_single_host_scan_compiles_for_table1_cached_ssd(table1_scan):
    _, compiled = table1_scan
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("runner", ["mld8_multi", "fleet_sharded"])
def test_mld8_multi_scan_carries_no_metrics_accumulator(runner, request):
    """With per-access streams the histogram, windows and media counters
    are folded on the host, on the unsharded and the sharded lane alike:
    the scan carries no ``(rows, 4)`` accumulator and scatters into
    none."""
    from repro.core.replay.metrics import acc_rows

    cfg, compiled = request.getfixturevalue(runner)
    hlo = compiled.as_text()
    rows = acc_rows(MetricsSpec(), cfg.num_hosts, cfg.num_devs)
    assert f"s64[{rows},4]" not in hlo
    assert not re.search(r"\bscatter\(", hlo)
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("runner",
                         ["table1_scan", "mld8_multi", "fleet_sharded"])
def test_table1_scan_has_no_int64_division(runner, request):
    """The step divides only by static powers of two, as shifts and masks:
    a TPU has no 64-bit divide, and XLA expands each int64 ``//`` or ``%``
    into some 1,700 serial scalar instructions on every step."""
    _, compiled = request.getfixturevalue(runner)
    divisions = re.findall(r'op_name="[^"]*jit\((?:floor_divide|remainder)\)',
                           compiled.as_text())
    assert not divisions, f"{len(divisions)} instructions expand a division"


@pytest.mark.parametrize("runner,span,pages", [
    ("mld8_multi", 1 << 22, 1 << 15),
    ("table1_scan", 1 << 16, 1 << 15),
    ("table1_sweep", 1 << 16, 1 << 14),
])
def test_ftl_map_is_sized_by_the_trace_not_the_span(runner, span, pages,
                                                   request):
    """The runner carries the FTL map at the trace's access count, one
    ``s32[lanes, pages]`` block per flash lane (a design point adds its
    own axis), and no map at the address span: at ``mld8.zipf``'s shapes
    a span-sized map is the whole 16 GiB device, 2^22 entries, and keeps
    the step's small arrays out of the core's fast memory."""
    cfg, compiled = request.getfixturevalue(runner)
    hlo = compiled.as_text()
    assert getattr(cfg, "stack", cfg).num_pages == pages
    assert re.search(rf"s32\[(\d+,)?1,{pages}\]", hlo)
    assert not re.search(rf"s32\[(\d+,)*{span}\]", hlo)


def test_sharded_fleet_runner_compiles_on_four_chip_mesh(topo,
                                                        fleet_sharded):
    _, compiled = fleet_sharded
    hlo = compiled.as_text()
    assert len(topo.devices) == 4
    assert "all-reduce" in hlo or "all-gather" in hlo
    assert _device_bytes(compiled) < HBM_BYTES
