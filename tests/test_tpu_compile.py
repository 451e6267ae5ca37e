"""Compile the main replay path for a described TPU v5e (2x2) without one.

The TPU compiler is installed with JAX, so it can compile for a chip that
is described and not attached: these tests refuse a kernel or program the
chip would refuse (tiling, memory spaces, unsupported reductions, device
memory) at no chip time.  Nothing runs, so they say nothing about results
or speed.  The topology is described inside a fixture, never at import:
only the worker that runs this file may load the TPU library.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.devices import make_device
from repro.core.fabric import Fabric
from repro.core.replay import MetricsSpec, ShardedMultiHostReplay, build_stack
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


def test_single_host_scan_compiles_for_table1_cached_ssd(one_chip):
    from repro.core.replay.engine import _run_stack

    n = 1 << 16
    addrs, writes = host_trace_np(ZIPF, 0, 0, n)
    with jax.enable_x64(True):
        cfg, params = build_stack(
            make_device("cxl-ssd-cache"), size=64, outstanding=32,
            issue_overhead_ns=0.5, posted_writes=True, n_accesses=n,
            max_addr=int(addrs.max()), counters=True)
        compiled = _run_stack.lower(
            cfg, _shapes(params, one_chip),
            jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
            1, MetricsSpec(), True, 64).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_fleet_runner_compiles_on_four_chip_mesh(topo):
    from repro.core.replay.shard import _build_runner

    hosts, n = 8, 2048
    fab = Fabric.build("multi_pod", ecmp=True, num_pods=2,
                       hosts_per_pod=hosts // 2)
    mounts = [fab.mount(f"h{i}", f"d{i}", make_device("cxl-ssd-cache"))
              for i in range(hosts)]
    eng = ShardedMultiHostReplay(mounts, metrics=MetricsSpec())
    addrs, writes = traces_np(ZIPF, 0, hosts, n)
    with jax.enable_x64(True):
        cfg, params, _, a, w, lens, size = eng.prepare_arrays(addrs, writes)
        sh, rep = eng._shard_tensors(cfg, params, lens, a, w)
        run, mesh = _build_runner(cfg, tuple(topo.devices), 1,
                                  MetricsSpec(), True, size)
        compiled = run.lower(
            jax.ShapeDtypeStruct((), jnp.int64,
                                 sharding=NamedSharding(mesh, P())),
            _shapes(sh, NamedSharding(mesh, P("hosts"))),
            _shapes(rep, NamedSharding(mesh, P()))).compile()
    hlo = compiled.as_text()
    assert len(topo.devices) == 4
    assert "all-reduce" in hlo or "all-gather" in hlo
    assert _device_bytes(compiled) < HBM_BYTES
