"""Pallas-kernel trace replay for the cached CXL-SSD (engine="pallas").

This is the accelerator-resident fast path: the fused Pallas kernel
(:func:`repro.kernels.cache_sim.cache_sim_fused`) replays the DRAM-cache
state machine and emits latency in the same sequential pass, with the cache
state held in VMEM scratch.

Fidelity contract (different from the scan engine's tick-exactness):

* hit / dirty-evict decisions are bit-identical to the vectorized cache
  oracle (:mod:`repro.core.cache.trace_sim`) and hence to the Python policy
  objects — the fully-associative LRU/FIFO cache maps to ``num_sets=1,
  ways=capacity``, direct-mapped to ``num_sets=capacity, ways=1``;
* latency follows a closed-loop analytic model (LFB-ring arrival throttling
  + fill-path busy-until queueing, nanosecond resolution) validated against
  :func:`repro.kernels.ref.cache_sim_fused_ref` — it tracks the shape of
  the exact replay but does not model MSHR coalescing, writeback stalls, or
  flash channel contention.  Use engine="scan" when ticks must match the
  interpreted driver exactly.
"""

from __future__ import annotations

import numpy as np

from repro.core.devices import CachedCXLSSDDevice
from repro.core.engine import TICKS_PER_NS
from repro.core.fabric.fabric import FabricAttachedDevice
from repro.core.replay.spec import ReplayUnsupported


def _cached_inner(device) -> CachedCXLSSDDevice:
    inner = device.inner if isinstance(device, FabricAttachedDevice) else device
    if not isinstance(inner, CachedCXLSSDDevice):
        raise ReplayUnsupported(
            "engine='pallas' models the cached CXL-SSD; use engine='scan' "
            f"for {type(inner).__name__}")
    return inner


def pallas_params(device, issue_overhead_ns: float) -> dict:
    """Derive the fused kernel's geometry + ns-resolution latency model
    from a live device."""
    inner = _cached_inner(device)
    cfg = inner.cache.cfg
    pol = inner.cache.policy.name
    if pol not in ("lru", "fifo", "direct"):
        raise ReplayUnsupported(f"pallas path supports lru/fifo/direct, "
                                f"got {pol!r}")
    frames = cfg.capacity_pages
    num_sets, ways = (frames, 1) if pol == "direct" else (1, frames)
    t = inner.hil.cfg.timing
    page = inner.hil.cfg.page_bytes
    miss_ns = (inner.hil.cfg.hil_overhead_ns + t.t_read_us * 1e3
               + page / t.channel_mbps * 1e3          # flash channel xfer
               + page / cfg.dram_bw_gbps              # cache-DRAM fill
               + cfg.hit_latency_ns)
    # A dirty eviction injects one flash program into the W-deep writeback
    # buffer; beyond its drain capacity the demand path stalls.  Amortize
    # that backpressure as program-time / W per dirty evict.
    wb_ns = (inner.hil.cfg.hil_overhead_ns
             + t.t_prog_us * 1e3) / max(1, cfg.writeback_buffer)
    return dict(num_sets=num_sets, ways=ways, policy=pol,
                issue_ns=max(1, int(round(issue_overhead_ns))),
                hit_ns=int(round(cfg.hit_latency_ns)),
                miss_ns=int(round(miss_ns)),
                miss_occ_ns=int(round(page / cfg.dram_bw_gbps)),
                wb_ns=int(round(wb_ns)))


def run_pallas(device, addrs: np.ndarray, writes: np.ndarray, *,
               size: int = 64, outstanding: int = 32,
               issue_overhead_ns: float = 0.5, start_tick: int = 0,
               interpret: bool | None = None, validate: bool = False):
    """Replay (addrs, writes) through the fused Pallas kernel; returns a
    :class:`~repro.core.replay.engine.ReplayResult`.

    ``interpret=None`` picks by backend: the compiled kernel on a TPU,
    interpret mode on the CPU, and an error anywhere else
    (:func:`repro.kernels.ops.interpret_mode`).

    ``validate=True`` recomputes the latency stream from the kernel's own
    decisions + arrivals through the associative busy-until formulation
    shared with the replay engines
    (:func:`repro.kernels.cache_sim.fill_latency_assoc`) and raises if the
    two disagree bit-for-bit — a cheap end-to-end cross-check of the
    in-kernel sequential chain, run on every golden-trace conformance
    pass."""
    from repro.core.replay.engine import ReplayResult
    from repro.kernels.cache_sim import cache_sim_fused
    from repro.kernels.ops import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    plan = getattr(device, "fault_plan", None)
    if plan is None:
        plan = getattr(getattr(device, "fabric", None), "fault_plan", None)
    if plan is not None and plan.active:
        raise ReplayUnsupported(
            f"active fault plan ({', '.join(plan.class_names())}): the "
            "pallas kernel models the fault-free cached CXL-SSD; the "
            "fused scan lane replays every fault class tick-identically "
            "— use engine='scan' (or engine='python')")
    kw = pallas_params(device, issue_overhead_ns)
    # int32-nanosecond budget: arrival/busy cursors grow by at most
    # (miss_occ + issue) per access, plus one service term on top.
    n = int(np.asarray(addrs).shape[-1])
    worst_ns = (n * (kw["miss_occ_ns"] + kw["issue_ns"])
                + kw["miss_ns"] + kw["wb_ns"])
    if worst_ns >= 2**31:
        raise ReplayUnsupported(
            f"trace of {n} accesses can overflow the kernel's int32 "
            f"nanosecond clock (worst case {worst_ns} ns); split the trace "
            "or use engine='scan'")
    pages64 = np.asarray(addrs, np.int64) // 4096
    if pages64.size and int(pages64.max()) >= 2**31:
        raise ReplayUnsupported(
            "page id exceeds the kernel's int32 tag range (addr >= 2^43); "
            "use engine='scan'")
    pages = pages64.astype(np.int32)
    hits, evicts, lat_ns, arr_ns = cache_sim_fused(
        pages, np.asarray(writes, bool), outstanding=max(1, outstanding),
        interpret=interpret, **kw)
    hits = np.asarray(hits)
    evicts = np.asarray(evicts)
    if validate:
        from repro.kernels.cache_sim import fill_latency_assoc
        lat2 = np.asarray(fill_latency_assoc(
            hits, evicts, arr_ns, hit_ns=kw["hit_ns"], miss_ns=kw["miss_ns"],
            miss_occ_ns=kw["miss_occ_ns"], wb_ns=kw["wb_ns"]))
        if not np.array_equal(lat2, np.asarray(lat_ns)):
            bad = int(np.flatnonzero(lat2 != np.asarray(lat_ns))[0])
            raise AssertionError(
                f"pallas kernel latency diverged from the associative "
                f"reconstruction at access {bad}: kernel "
                f"{int(np.asarray(lat_ns)[bad])}, assoc {int(lat2[bad])}")
    lat = np.asarray(lat_ns).astype(np.int64) * TICKS_PER_NS
    issues = start_tick + np.asarray(arr_ns).astype(np.int64) * TICKS_PER_NS
    dones = issues + lat
    n = pages.size
    return ReplayResult(
        accesses=n, bytes_moved=n * size,
        elapsed_ticks=int(dones.max(initial=start_tick) - issues[0]),
        sum_latency_ticks=int(lat.sum()),
        end_tick=int(dones.max(initial=start_tick)),
        latency_ticks=lat, hit_flags=hits, evict_flags=evicts)
