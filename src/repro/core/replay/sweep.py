"""vmap-batched design-space sweeps over the fused replay engine.

One compiled call evaluates a whole batch of simulator configurations
against the same (or per-lane) traces:

* :func:`cache_design_sweep` — batch over DRAM-cache **capacity**
  (disabled-frame masking inside a fixed frame array), **policy**
  (LRU/FIFO via the traced ``is_lru`` flag), and any **timing constant**
  (hit latency, link occupancy, flash timing, ...), on the full
  cached-CXL-SSD stack.  Each lane runs the same tick-exact step function
  the single-config engine runs, so lane *k* of the batch equals a
  standalone :class:`~repro.core.replay.engine.ReplayEngine` run with that
  config (tested).
* :func:`host_count_sweep` — batch over **host count** on the fused
  multi-host replay: one compiled program, one vmap lane per host count,
  inactive hosts masked out of the issue race by zero-length traces
  (``sharded=True`` instead reuses one cached shard_map program — the
  masked lengths are traced — across every host count sharing the shard
  shape).
* :func:`fault_seed_sweep` — batch over **fault-plan seed** on the fused
  multi-host replay under an active transport fault plan: the per-seed
  precomputed hop columns (retry-stretched occupancies, failover routes)
  are the ONLY batched leaves, so one compiled program yields the full
  tail-latency-under-failure / availability distribution across seeds.

On CPU these amortize compile time and per-step dispatch; on TPU/GPU the
lanes vectorize across the batch dimension, which is where the
design-space throughput multiplier comes from.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64
from jax.profiler import TraceAnnotation

from repro.core.replay import stack
from repro.core.replay.engine import _scan_stack
from repro.core.replay.metrics import availability_series
from repro.core.replay.multihost import MultiHostReplay, _run_multi
from repro.core.replay.spec import SSD_CACHE, ReplayUnsupported, build_stack
from repro.core.replay.stack import MAX_ACCESSES, PAGE_FIELD, _i64
from repro.core.workloads.driver import MultiHostResult
from repro.obs import scopes

# A disabled frame: never matches (page field all-ones is reserved) and is
# never chosen as victim (above every valid packed value and every -1).
DISABLED = (1 << 62) | PAGE_FIELD


# Module-level jitted runners (like engine._run_stack / multihost._run_multi)
# so repeated sweep calls with the same static shape hit the compile cache.
@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _run_cache_lanes(cfg, pj: Dict, trace_args, batched: frozenset,
                     trace_ax):
    axes = {k: (0 if k in batched else None) for k in pj}
    a, w = trace_args

    def one(p1, a1, w1):
        st = stack.init_state(cfg)
        frames = jnp.where(
            jnp.arange(cfg.cache_frames) < p1["cap"],
            jnp.asarray(-1, jnp.int64),
            jnp.asarray(DISABLED, jnp.int64))
        st = {**st, "media": {**st["media"], "frames": frames[None]}}
        return _scan_stack(cfg, p1, st, a1, w1, _i64(0))

    return jax.vmap(one, in_axes=(axes, trace_ax, trace_ax))(pj, a, w)


#: the per-seed transport-fault hop columns — the only params leaves that
#: change with the FaultPlan seed (down segments, and hence every static
#: shape, come from the FaultConfig alone)
_FAULT_KEYS = ("fhp", "fho", "fha", "fhon", "fhoc")


@functools.partial(jax.jit, static_argnums=(0, 6))
def _run_fault_lanes(cfg, pj: Dict, devs, addrs, writes, lens,
                     batched: frozenset):
    axes = {k: (0 if k in batched else None) for k in pj}
    return jax.vmap(
        lambda p1: _run_multi(cfg, p1, devs, addrs, writes, lens, _i64(0)),
        in_axes=(axes,))(pj)


@functools.partial(jax.jit, static_argnums=(0,))
def _run_multi_lanes(cfg, pj: Dict, devs, addrs, writes, lane_lens):
    return jax.vmap(
        lambda lens_k: _run_multi(cfg, pj, devs, addrs, writes, lens_k,
                                  _i64(0)))(lane_lens)


def cache_design_sweep(device, addrs, writes, *,
                       capacity_frames: Sequence[int],
                       is_lru: Sequence[bool],
                       timing_overrides: Optional[Dict[str, Sequence]] = None,
                       outstanding: int = 32,
                       issue_overhead_ns: float = 0.5,
                       posted_writes: bool = True) -> Dict[str, np.ndarray]:
    """Replay a trace under B cached-device configs in one compiled call.

    ``capacity_frames[k]`` / ``is_lru[k]`` / ``timing_overrides[name][k]``
    describe lane k; all sequences must share length B.  ``device`` provides
    the base config and must have ``capacity_pages >= max(capacity_frames)``.
    ``addrs``/``writes`` may be (N,) — shared by every lane — or (B, N) for
    per-lane traces.  Returns stacked per-lane arrays (``latency_ticks``,
    ``hit_flags`` of shape (B, N)) plus derived (B,) summaries.
    """
    addrs = np.asarray(addrs, np.int64)
    writes = np.asarray(writes, bool)
    caps = np.asarray(capacity_frames, np.int64)
    lru = np.asarray(is_lru, bool)
    B = caps.size
    if lru.size != B:
        raise ValueError("capacity_frames and is_lru must share a length")
    if addrs.shape[-1] > MAX_ACCESSES:
        raise ReplayUnsupported(
            f"trace longer than {MAX_ACCESSES} accesses (packed-stamp "
            "budget); split the trace")
    with TraceAnnotation("replay.build"):
        cfg, params = build_stack(
            device, size=64, outstanding=outstanding,
            issue_overhead_ns=issue_overhead_ns, posted_writes=posted_writes,
            n_accesses=addrs.shape[-1], max_addr=int(addrs.max(initial=0)))
    if cfg.kind != SSD_CACHE:
        raise ReplayUnsupported("cache_design_sweep needs a cached CXL-SSD")
    if not cfg.cache_assoc:
        raise ReplayUnsupported(
            "the policy axis covers lru/fifo; sweep direct-mapped separately")
    if caps.max() > cfg.cache_frames or caps.min() < 1:
        raise ReplayUnsupported("capacity lane exceeds the device's frames")
    params["is_lru"] = lru
    params["cap"] = caps
    batched = {"is_lru", "cap"}
    for name, vals in (timing_overrides or {}).items():
        if name not in params:
            raise ValueError(f"unknown timing parameter {name!r}")
        vals = np.asarray(vals)
        if vals.shape[0] != B:
            raise ValueError(f"override {name!r} must have {B} lanes")
        params[name] = vals
        batched.add(name)

    trace_ax = 0 if addrs.ndim == 2 else None
    with enable_x64(True):
        with TraceAnnotation("replay.put"):
            pj = {k: jnp.asarray(v) for k, v in params.items()}
            xs = (jnp.asarray(addrs), jnp.asarray(writes))
        with TraceAnnotation("replay.run"):
            issues, dones, flags, final, _ = scopes.run(
                _run_cache_lanes, cfg, pj, xs, frozenset(batched), trace_ax)
        with TraceAnnotation("replay.fetch"):
            issues = np.asarray(issues)
            dones = np.asarray(dones)
            flags = np.asarray(flags)
        flash = final["flash"]
        if flash is not None and "bad" in flash:
            # certify-or-refuse, per lane: a lane whose FTL ran out of free
            # blocks during GC replayed past the point where the
            # interpreted path raises — its numbers must not escape
            bad_lanes = [k for k, b in
                         enumerate(np.asarray(flash["bad"]).reshape(B, -1))
                         if b.any()]
            if bad_lanes:
                raise ReplayUnsupported(
                    f"sweep lane(s) {bad_lanes}: FTL ran out of free blocks "
                    "during GC (device overfilled); use engine='python'")
    with TraceAnnotation("replay.finish"):
        lat = dones - issues
        return {
            "latency_ticks": lat,
            "hit_flags": (flags & 1).astype(bool),
            "evict_flags": (flags & 2).astype(bool),
            "sum_latency_ticks": lat.sum(axis=1),
            "hit_rate": (flags & 1).mean(axis=1),
            "elapsed_ticks": dones.max(axis=1) - issues[:, 0],
        }


def host_count_sweep(targets: Sequence, traces: Sequence,
                     host_counts: Sequence[int],
                     outstanding: int = 32,
                     issue_overhead_ns: float = 0.5,
                     posted_writes: bool = True,
                     sharded: bool = False,
                     devices: Optional[Sequence] = None,
                     info: Optional[Dict] = None) -> List[MultiHostResult]:
    """Replay the same multi-host scenario at several host counts with ONE
    compiled program.

    ``targets``/``traces`` describe the largest configuration; lane k keeps
    the first ``host_counts[k]`` hosts and masks the rest out with
    zero-length traces (an absent host issues nothing, so the shared-port
    and media contention it would have caused never happens — identical to
    running the smaller scenario).  Lane k is tick-identical to
    ``MultiHostReplay(targets[:k]).run(traces[:k])`` over the *same shared
    fabric* (tested against :class:`MultiHostDriver`).  Any stack-layer
    media works, cached CXL-SSD included — absent hosts leave their private
    cache lanes (and the shared flash) untouched.

    ``sharded=True`` runs each host count through
    :class:`~repro.core.replay.shard.ShardedMultiHostReplay` on ``devices``
    (default ``jax.devices()``): the masked length vector is a *traced*
    argument of the cached shard_map program, so every host count sharing
    the shard shape reuses one compiled program — the same amortization the
    unsharded path gets from vmap lanes, at ``~H/D`` per-device state.
    Pass a dict as ``info`` to receive the execution report
    (``{"sharded", "device_count", "hosts_per_device"}``).
    """
    if sharded:
        from repro.core.replay.shard import ShardedMultiHostReplay
        eng = ShardedMultiHostReplay(targets, outstanding=outstanding,
                                     issue_overhead_ns=issue_overhead_ns,
                                     posted_writes=posted_writes,
                                     devices=devices)
        cfg, params, devs, addrs, writes, lens, size = eng.prepare(traces)
        out: List[MultiHostResult] = []
        with enable_x64(True):
            for h in host_counts:
                lane = np.where(np.arange(lens.size) < h, lens, 0)
                who, issues, dones, bad, _, _ = eng._dispatch(
                    cfg, params, devs, addrs, writes, lane, 0,
                    None, True, size, None)
                who = np.asarray(who)
                issues = np.asarray(issues)
                dones = np.asarray(dones)
                total = int(lane.sum())
                if total and bool(np.asarray(bad)[total - 1]):
                    raise ReplayUnsupported(
                        f"host-count lane {h}: FTL ran out of free blocks "
                        "during GC; use engine='python'")
                out.append(eng.aggregate(who, issues, dones, lane, size))
        if info is not None:
            info.update(dict(eng.last_mesh, sharded=True))
        return out
    eng = MultiHostReplay(targets, outstanding=outstanding,
                          issue_overhead_ns=issue_overhead_ns,
                          posted_writes=posted_writes)
    cfg, params, devs, addrs, writes, lens, size = eng.prepare(traces)
    if info is not None:
        info.update({"sharded": False, "device_count": 1,
                     "hosts_per_device": int(lens.size)})
    lane_lens = np.stack([
        np.where(np.arange(lens.size) < h, lens, 0) for h in host_counts])
    with enable_x64(True):
        pj = jax.tree.map(jnp.asarray, params)
        who, issues, dones, bad, _, _ = scopes.run(
            _run_multi_lanes, cfg, pj, jnp.asarray(devs), jnp.asarray(addrs),
            jnp.asarray(writes), jnp.asarray(lane_lens))
        who = np.asarray(who)
        issues = np.asarray(issues)
        dones = np.asarray(dones)
        bad = np.asarray(bad)
    for k in range(len(host_counts)):
        total = int(lane_lens[k].sum())
        if total and bool(bad[k, total - 1]):
            raise ReplayUnsupported(
                f"host-count lane {host_counts[k]}: FTL ran out of free "
                "blocks during GC; use engine='python'")
    return [eng.aggregate(who[k], issues[k], dones[k], lane_lens[k], size)
            for k in range(len(host_counts))]


def fault_seed_sweep(make_targets, traces: Sequence, seeds: Sequence[int],
                     *, outstanding: int = 32,
                     issue_overhead_ns: float = 0.5,
                     posted_writes: bool = True,
                     window_ticks: Optional[int] = None,
                     num_windows: int = 32) -> List[Dict]:
    """Replay one multi-host scenario under B transport-fault seeds in ONE
    compiled vmapped call — the fleet-scale availability sweep.

    ``make_targets(seed)`` builds fresh fabric-mounted targets with a
    ``FaultPlan(cfg, seed=seed)`` installed; every seed must share the
    FaultConfig (down windows and the derived hop/port shapes are config
    properties — a seed that changed them could not share the compiled
    program, and the sweep refuses).  Only the precomputed per-access hop
    columns (retry-stretched occupancies, failover paths) differ across
    lanes, so they are the sole batched leaves.

    Lane k is tick-identical to
    ``MultiHostReplay(make_targets(seeds[k])).run(traces)`` (and hence to
    the interpreted ``MultiHostDriver``).  Each returned dict carries the
    per-seed ``result`` (:class:`MultiHostResult`), pooled
    ``latency_ticks`` (valid accesses, global issue order),
    ``availability`` (:func:`~repro.core.replay.metrics.availability_series`
    over the pooled per-access degraded/failover flags) and the
    ``fault_stats`` counter dict.  With ``window_ticks=None`` the window
    width is derived from the batch (max completion tick over all lanes /
    ``num_windows``) so every lane's availability curve shares one axis.
    """
    cfg0 = base = devs = addrs = writes = lens = None
    size = 0
    stacked: Dict[str, List] = {k: [] for k in _FAULT_KEYS}
    flags, stats = [], []
    for s in seeds:
        eng = MultiHostReplay(make_targets(s), outstanding=outstanding,
                              issue_overhead_ns=issue_overhead_ns,
                              posted_writes=posted_writes)
        cfg, params, dv, ad, wr, ln, sz = eng.prepare(traces)
        if not cfg.fault_hops:
            raise ReplayUnsupported(
                "fault_seed_sweep needs an active transport fault plan "
                "(link-retry and/or down-window classes) installed on the "
                "shared fabric; for fault-free host scaling use "
                "host_count_sweep")
        if cfg0 is None:
            cfg0, base, devs, addrs, writes, lens, size = \
                cfg, params, dv, ad, wr, ln, sz
        elif cfg != cfg0:
            raise ReplayUnsupported(
                "fault seeds changed the compiled shape — down windows "
                "(and the hop/port geometry they induce) must come from "
                "the shared FaultConfig, not the per-lane seed")
        for k in _FAULT_KEYS:
            stacked[k].append(params[k])
        flags.append(eng.fault_flags)
        stats.append(dict(eng._meta["fault_stats"]))
    pj = dict(base)
    for k in _FAULT_KEYS:
        pj[k] = np.stack(stacked[k])
    with enable_x64(True):
        pj = jax.tree.map(jnp.asarray, pj)
        who, issues, dones, bad, _, _ = _run_fault_lanes(
            cfg0, pj, jnp.asarray(devs), jnp.asarray(addrs),
            jnp.asarray(writes), jnp.asarray(lens),
            frozenset(_FAULT_KEYS))
        who = np.asarray(who)
        issues = np.asarray(issues)
        dones = np.asarray(dones)
        bad = np.asarray(bad)
    lens = np.asarray(lens)
    total = int(lens.sum())
    valid = np.arange(who.shape[1]) < total
    if window_ticks is None:
        max_end = int(dones[:, valid].max(initial=0)) if total else 1
        window_ticks = max(1, -(-max_end // num_windows))
    out: List[Dict] = []
    for k, s in enumerate(seeds):
        if total and bool(bad[k, total - 1]):
            raise ReplayUnsupported(
                f"fault seed lane {s}: FTL ran out of free blocks during "
                "GC (device overfilled); use engine='python'")
        res = MultiHostReplay.aggregate(who[k], issues[k], dones[k],
                                        lens, size)
        deg, fo = flags[k]
        iss_h, dn_h, deg_h, fo_h = [], [], [], []
        for i in range(lens.size):
            m = valid & (who[k] == i)
            iss_h.append(issues[k][m])
            dn_h.append(dones[k][m])
            deg_h.append(deg[i, :lens[i]])
            fo_h.append(fo[i, :lens[i]])
        iss = np.concatenate(iss_h)
        dn = np.concatenate(dn_h)
        av = availability_series(iss, dn, np.concatenate(deg_h),
                                 np.concatenate(fo_h),
                                 window_ticks=window_ticks,
                                 num_windows=num_windows)
        out.append({"seed": int(s), "result": res,
                    "latency_ticks": dn - iss,
                    "availability": av, "fault_stats": stats[k]})
    return out
