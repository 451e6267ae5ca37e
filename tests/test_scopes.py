"""Named step scopes of the fused replay (``repro.obs.scopes``).

The compiled replay programs map their instructions onto the step's parts,
the recording hook keeps the optimized HLO of the program that ran (and
costs nothing outside its block), and the jitted runners keep the names
that a profiler trace gives their modules.  One small cached CXL-SSD job
per runner is compiled once for the whole file."""

import jax
import numpy as np
import pytest

from repro.core.cache.dram_cache import DRAMCacheConfig
from repro.core.devices import CachedCXLSSDDevice
from repro.core.replay import (MetricsSpec, ReplayEngine, cache_design_sweep,
                               engine, multihost, sweep)
from repro.obs import scopes

N = 192
STEP_PARTS = {"lfb", "transport", "media", "flash", "telemetry"}
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def _device():
    return CachedCXLSSDDevice(cache_cfg=DRAMCacheConfig(
        capacity_bytes=32 * 4096, mshr_entries=4, writeback_buffer=2))


def _scan_job(addrs, writes):
    return ReplayEngine(_device(), metrics=MetricsSpec(
        hist_buckets=16, window_ticks=10**6, num_windows=4)).run_arrays(
            addrs, writes)


def _sweep_job(addrs, writes):
    return cache_design_sweep(_device(), addrs, writes,
                              capacity_frames=[8, 32], is_lru=[True, False])


JOBS = {"_run_stack": _scan_job, "_run_cache_lanes": _sweep_job}


@pytest.fixture(scope="module")
def trace():
    rng = np.random.default_rng(7)
    return (rng.integers(0, 96, N) * 4096 + 64 * rng.integers(0, 64, N),
            rng.random(N) < 0.5)


@pytest.fixture(scope="module")
def kept(trace):
    with scopes.recording() as k:
        for job in JOBS.values():
            job(*trace)
    return k


@pytest.mark.parametrize("runner", sorted(JOBS))
def test_recording_keeps_the_program_under_the_runner_name(kept, runner):
    assert kept[runner].startswith(f"HloModule jit_{runner}")


@pytest.mark.parametrize("runner", sorted(JOBS))
def test_compiled_step_maps_to_every_part(kept, runner):
    got = scopes.op_scopes(kept[runner])
    assert STEP_PARTS <= set(got.values())
    assert set(got.values()) <= {*scopes.STEP_SCOPES, scopes.LOOP,
                                 scopes.UNNAMED}


def test_outside_recording_nothing_is_kept_or_compiled(kept, trace):
    events = []

    def listen(event, duration, **kw):
        if event in COMPILE_EVENTS:
            events.append(event)

    before = dict(kept)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for job in JOBS.values():
            job(*trace)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert events == []
    assert kept == before and scopes._kept is None


@pytest.mark.parametrize("module, name", [
    (engine, "_run_stack"), (engine, "_run_stack_ecmp"),
    (engine, "_run_stack_faulted"), (engine, "_replay_chunk"),
    (multihost, "_run_multi"), (multihost, "_run_multi_chunk"),
    (sweep, "_run_cache_lanes"), (sweep, "_run_multi_lanes")])
def test_runner_names_are_stable(module, name):
    # the benchmark's lanes find their runner's module in a device trace
    # (``jit_<name>``) and its recorded program by these names
    assert getattr(module, name).__name__ == name


HLO = """HloModule jit_toy, entry_computation_layout={()->u32[4]}

%fused_computation (param_0: u32[4]) -> u32[4] {
  %param_0 = u32[4]{0} parameter(0)
  ROOT %add.1 = u32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(toy)/while/body/media/flash/add"}
}

%body (arg: (u32[4], u32[4])) -> (u32[4], u32[4]) {
  %arg = (u32[4]{0}, u32[4]{0}) parameter(0)
  %get-tuple-element.1 = u32[4]{0} get-tuple-element(%arg), index=0
  %copy-start.1 = (u32[4]{0}, u32[4]{0}, u32[]) copy-start(%get-tuple-element.1)
  %copy-done.1 = u32[4]{0} copy-done(%copy-start.1)
  %fusion.1 = u32[4]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation
  %multiply.2 = u32[4]{0} multiply(%fusion.1, %fusion.1), metadata={op_name="jit(toy)/while/body/lfb/mul;jit(toy)/while/body/lfb/mul"}
  %subtract.3 = u32[4]{0} subtract(%multiply.2, %fusion.1)
  %negate.4 = u32[4]{0} negate(%get-tuple-element.1), metadata={op_name="jit(toy)/while/body/neg"}
  ROOT %tuple.5 = (u32[4]{0}, u32[4]{0}) tuple(%subtract.3, %negate.4)
}
"""


@pytest.mark.parametrize("infer, want", [
    (True, {"add.1": "flash", "fusion.1": "flash", "multiply.2": "lfb",
            "negate.4": "loop", "copy-done.1": "flash",
            "copy-start.1": "flash", "subtract.3": "lfb",
            "get-tuple-element.1": "loop"}),
    (False, {"add.1": "flash", "fusion.1": "flash", "multiply.2": "lfb",
             "negate.4": "loop", "copy-done.1": "unnamed",
             "copy-start.1": "unnamed", "subtract.3": "unnamed",
             "get-tuple-element.1": "unnamed"})])
def test_op_scopes_rules(infer, want):
    got = scopes.op_scopes(HLO, infer=infer)
    assert {k: got[k] for k in want} == want
