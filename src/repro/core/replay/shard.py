"""Rack-scale sharded fused multi-host replay: ``shard_map`` over the host
axis.

:class:`ShardedMultiHostReplay` partitions the leading host axis of the
fused multi-host scan — the per-host LFB slots / clocks / trace cursors,
the :mod:`repro.core.replay.stack` media and (private) flash lanes, and the
per-host trace / route / fault columns — across ``D`` JAX devices with
``jax.shard_map``, so an ``H``-host replay holds ``~H/D`` per-device
state (inputs included: the host-sharded columns are placed shard by
shard, never whole on one device).  The *shared* simulator state stays
explicitly replicated: the per-port busy-until vector, the QoS
virtual-finish / last-arrival tables and the global stamp counter are
updated identically on every shard from broadcast winner inputs, so
replicas never diverge.

Every shard runs the unsharded lane's step,
:func:`repro.core.replay.multihost._make_multi_step`, over its own carry
(:func:`~repro.core.replay.multihost._multi_init` at ``H/D`` hosts).  This
module supplies only the step's pick, two collectives per scan step that
mirror the global issue order exactly:

1. **winner election** — each shard races its local hosts
   (``max(own clock, oldest LFB slot)``, ties to the lowest local index)
   and ``all_gather``\\ s its ``(candidate tick, local index)`` pair; the
   argmin over shard minima (ties to the lowest shard) reproduces the
   interpreted heap's global ``(tick, host index)`` order *exactly*,
   because hosts are block-assigned to shards (host ``i`` lives on shard
   ``i // (H/D)`` — the same block assignment the ``multi_pod`` topology
   builder uses for pods).
2. **record broadcast** — the owning shard packs the winner's access
   ``(addr, write)`` plus its per-hop transport rows (port index, charged
   and clean occupancy, post-hop latency, on-mask) into one int64 vector
   and zero-gated ``psum`` broadcasts it; every shard then walks the same
   shared-port / QoS-mirror update — replicated arithmetic on replicated
   state.

The pick's ownership gate confines the media writeback
(:func:`repro.core.replay.stack.step`'s ``en``), the LFB commit and the
in-scan accumulators to the owning shard; the media step itself runs
SPMD-lockstep on every shard.  After the scan the per-step ``done`` and
flags columns are owner-gated and summed over shards, and the per-shard
counters folded, so every output is replicated.  Metrics collect as on
the unsharded lane: folded on the host from the streams when there are
per-access outputs, else from the in-scan accumulator.

**Certify or refuse.**  The sharded lane is tick-identical (latencies,
MetricsBundle, fault counters) to :class:`MultiHostReplay` — and hence to
the interpreted :class:`MultiHostDriver` — for per-host fabric *mounts*
over any stack medium with *private* flash, QoS / ECMP / transport-fault
columns included (property-tested at H in {2, 8, 32}).  Pooled views
(one address space interleaved across shards) and shared-flash HILs
(one flash state coupled across shards every step) refuse with the
widest covering lane named, as does ``chunk_size`` (stream per shard or
use the unsharded chunked lane).

On a CPU dev box, force a multi-device host platform with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* importing
jax; the shard count is the largest divisor of ``H`` not exceeding the
available (or passed) devices, so any H runs on any box — ``D=1`` is the
degenerate single-shard program, still the exact same SPMD code path.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.replay.multihost import (BIG, FAULT_KEYS, MultiCfg,
                                         MultiHostReplay, Pick,
                                         _make_multi_step, _multi_init,
                                         _split_ys)
from repro.core.replay.spec import ReplayUnsupported
from repro.core.replay.stack import _i64


def shard_count(num_hosts: int, devices: Optional[Sequence] = None) -> int:
    """The shard count used for ``num_hosts``: the largest divisor of the
    host count that does not exceed the available (or given) devices."""
    n = len(devices) if devices is not None else jax.device_count()
    d = max(1, min(n, num_hosts))
    while num_hosts % d:
        d -= 1
    return d


def _elect(cfg: MultiCfg, Hl: int, sh: Dict, slots, now, idx) -> Pick:
    """The sharded lane's pick: the global winner by election, its access
    record broadcast from the shard that owns it."""
    L = sh["addrs"].shape[1]
    MH = cfg.max_hops
    me = jax.lax.axis_index("hosts")
    with jax.named_scope("lfb"):
        cand = jnp.where(idx < sh["lens"],
                         jnp.maximum(now, jnp.min(slots, axis=1)), BIG)
        li0 = jnp.argmin(cand)
    with jax.named_scope("collective"):
        g = jax.lax.all_gather(
            jnp.stack([cand[li0], li0.astype(jnp.int64)]), "hosts")
    with jax.named_scope("lfb"):
        w = jnp.argmin(g[:, 0])            # ties -> lowest shard
        li = g[w, 1]                       # winner's local row (owner shard)
        valid = g[w, 0] < BIG
        own = (me == w) & valid
        k = jnp.argmin(slots[li])
    ix = jnp.clip(idx[li], 0, L - 1)
    if cfg.fault_hops:
        cols = [sh[c][li, ix] for c in ("fhon", "fhp", "fho", "fha", "fhoc")]
    else:
        r = sh["route"][li, ix] if cfg.max_routes > 1 else 0
        cols = [sh[c][li, r] for c in ("hop_on", "hop_port", "hop_occ",
                                       "hop_after", "hop_occ")]
    rec = jnp.concatenate(
        [jnp.stack([sh["addrs"][li, ix],
                    sh["writes"][li, ix].astype(jnp.int64)])]
        + [c.astype(jnp.int64) for c in cols])
    with jax.named_scope("collective"):
        rec = jax.lax.psum(jnp.where(own, rec, 0), "hosts")

    def hop(h):
        on, pi, occ, after, occ_c = (rec[2 + c * MH + h] for c in range(5))
        return on > 0, pi, occ, occ_c, after

    host = w * Hl + li
    return Pick(host=host, row=li, slot=k,
                issue=jnp.where(valid, g[w, 0], _i64(0)), valid=valid,
                addr=rec[0], write=rec[1] > 0, dev=host, lane=li,
                flash_lane=li, hop=hop, own=own)


def _body(cfg: MultiCfg, D: int, mspec, want_lat: bool, size: int,
          block: int, start_tick, sh: Dict, rep: Dict):
    """The per-shard program: the shared multi-host step over this shard's
    carry, and the post-scan reductions that make every output
    replicated."""
    H = cfg.num_hosts
    Hl = H // D
    init = _multi_init(cfg, start_tick, mspec, want_lat, local=Hl)
    step = _make_multi_step(cfg, rep, functools.partial(_elect, cfg, Hl, sh),
                            mspec, want_lat, size)
    carry, ys = jax.lax.scan(step, init, None,
                             length=H * sh["addrs"].shape[1], unroll=block)
    who, issues, dones, bad, gcs, aux = _split_ys(ys, carry[8])
    with jax.named_scope("collective"):
        if want_lat:
            lo = jax.lax.axis_index("hosts") * Hl
            mine = (who >= lo) & (who < lo + Hl)
            dones = jax.lax.psum(jnp.where(mine, dones, 0), "hosts")
            if "flags" in aux:
                aux = {**aux, "flags": jax.lax.psum(
                    jnp.where(mine, aux["flags"], 0), "hosts")}
            bad = jax.lax.psum(jnp.where(bad, 1, 0), "hosts") > 0
            gcs = jax.lax.psum(gcs, "hosts")
        if "acc" in aux:
            aux = {**aux,
                   "acc": jax.lax.psum(aux["acc"], "hosts"),
                   "med": jax.lax.psum(aux["med"], "hosts")}
        if "flash" in aux:
            aux = {**aux, "flash": jax.lax.all_gather(
                aux["flash"], "hosts").reshape(H, -1)}
        if "faults" in aux:
            aux = {**aux, "faults": jax.lax.psum(aux["faults"], "hosts")}
        if not want_lat:
            gathered = {k: jax.lax.all_gather(aux[k], "hosts").reshape(H)
                        for k in ("first", "last", "sum", "cnt")}
            aux = {**aux, **gathered,
                   "bad": jax.lax.psum(
                       jnp.where(aux["bad"], 1, 0), "hosts") > 0,
                   "gcs": jax.lax.psum(aux["gcs"], "hosts")}
    return who, issues, dones, bad, gcs, aux


@functools.lru_cache(maxsize=64)
def _build_runner(cfg: MultiCfg, devices: Tuple, block: int, mspec,
                  want_lat: bool, size: int):
    """One jitted shard_map program per (static shape, device set) — cached
    so sweeps and repeated runs (including traced-``lens`` reuse across
    host counts) never recompile."""
    mesh = Mesh(np.array(devices), ("hosts",))
    D = len(devices)
    body = functools.partial(_body, cfg, D, mspec, want_lat, size, block)
    f = shard_map(body, mesh=mesh, in_specs=(P(), P("hosts"), P()),
                  out_specs=P(), check_vma=False)
    return jax.jit(f), mesh


class ShardedMultiHostReplay(MultiHostReplay):
    """:class:`MultiHostReplay` with the host axis sharded across devices
    (see the module docstring for the SPMD structure and the exactness /
    refusal contract).  ``devices=None`` uses ``jax.devices()``; the shard
    count is :func:`shard_count` of the host count.  ``last_mesh`` reports
    ``{"device_count", "hosts_per_device"}`` after a run."""

    def __init__(self, targets: Sequence, outstanding: int = 32,
                 issue_overhead_ns: float = 0.5,
                 posted_writes: bool = True, block_size: int = 1,
                 metrics=None, devices: Optional[Sequence] = None) -> None:
        super().__init__(targets, outstanding=outstanding,
                         issue_overhead_ns=issue_overhead_ns,
                         posted_writes=posted_writes, block_size=block_size,
                         metrics=metrics)
        self.devices = tuple(devices) if devices is not None else None
        self.last_mesh = None

    def _shard_tensors(self, cfg, params, lens, addrs, writes):
        """Split the prepared tensors into the host-sharded dict and the
        replicated dict (compacting the mount-diagonal hop tensors from
        ``(H, H, K, max_hops)`` to ``(H, K, max_hops)`` — the O(H^2) -> O(H)
        reduction that makes fleet-scale routing state shardable)."""
        H = cfg.num_hosts
        sh = {"addrs": np.ascontiguousarray(addrs),
              "writes": np.ascontiguousarray(writes),
              "lens": np.asarray(lens, np.int64)}
        if cfg.fault_hops:
            for k in FAULT_KEYS:
                sh[k] = params[k]
        else:
            diag = np.arange(H)
            for k in ("hop_port", "hop_occ", "hop_after", "hop_on"):
                sh[k] = np.ascontiguousarray(params[k][diag, diag])
            if cfg.max_routes > 1:
                sh["route"] = params["route"]
        skip = {"hop_port", "hop_occ", "hop_after", "hop_on", "route",
                "flash_of", *FAULT_KEYS}
        rep = {k: v for k, v in params.items() if k not in skip}
        return sh, rep

    def _dispatch(self, cfg, params, devs, addrs, writes, lens, start_tick,
                  mspec, want_lat, size, chunk_size):
        if chunk_size is not None:
            raise ReplayUnsupported(
                "sharded multi-host replay is one-shot (per-host columns "
                "already live device-side); use MultiHostReplay with "
                "chunk_size= for streaming, or stream per shard")
        meta = self._meta
        if meta["mapper"] is not None:
            raise ReplayUnsupported(
                "sharded replay partitions per-host fabric mounts; pool "
                "views interleave one address space across every shard — "
                "use the unsharded MultiHostReplay lane")
        H = cfg.num_hosts
        if cfg.n_flash and cfg.n_flash != H:
            raise ReplayUnsupported(
                "sharded replay needs a private flash per host (a shared "
                "HIL couples every shard's state on every step); use the "
                "unsharded MultiHostReplay lane for pooled flash")
        if cfg.num_devs != H:
            raise ReplayUnsupported(
                "sharded replay expects one mounted device per host")
        devices = (self.devices if self.devices is not None
                   else tuple(jax.devices()))
        D = shard_count(H, devices)
        mesh_devs = tuple(devices[:D])
        self.last_mesh = {"device_count": D, "hosts_per_device": H // D}
        sh, rep = self._shard_tensors(cfg, params, lens, addrs, writes)
        run, mesh = _build_runner(cfg, mesh_devs, self.block_size, mspec,
                                  want_lat, size)
        # place each shard on its own device (never the whole host axis
        # on one) and the shared tensors on every device
        sh = jax.device_put(sh, NamedSharding(mesh, P("hosts")))
        rep = jax.device_put(rep, NamedSharding(mesh, P()))
        return run(_i64(start_tick), sh, rep)
