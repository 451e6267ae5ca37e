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

Two collectives per scan step mirror the global issue order exactly:

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
   and clean occupancy, post-hop latency, on-mask) into one int64 vector,
   zero-gated ``psum`` broadcasts it, and every shard then walks the same
   shared-port / QoS-mirror update the unsharded lane walks — replicated
   arithmetic on replicated state.

The media step runs SPMD-lockstep on every shard (``lax.cond`` branches
diverge per shard, which is fine — there is no collective inside the
stack), with the lane *writeback* gated to the owner via
:func:`repro.core.replay.stack.step`'s ``en`` flag; every use of the
non-owner's garbage outputs is owner-gated before it reaches an
accumulator.  Padded trailing steps broadcast a zero record (no port or
QoS mutation) — valid outputs are unaffected, exactly like the unsharded
lane's discarded trailing steps.

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

from repro.core.fabric.switch import ACTIVE_WINDOW_OCC
from repro.core.replay import stack
from repro.core.replay.multihost import BIG, NEVER, MultiCfg, MultiHostReplay
from repro.core.replay.spec import DRAM, ReplayUnsupported
from repro.core.replay.stack import _i64

#: params leaves that are sharded along the host axis (everything else in
#: the params dict rides replicated)
_FAULT_KEYS = ("fhp", "fho", "fha", "fhon", "fhoc")


def shard_count(num_hosts: int, devices: Optional[Sequence] = None) -> int:
    """The shard count used for ``num_hosts``: the largest divisor of the
    host count that does not exceed the available (or given) devices."""
    n = len(devices) if devices is not None else jax.device_count()
    d = max(1, min(n, num_hosts))
    while num_hosts % d:
        d -= 1
    return d


def _body(cfg: MultiCfg, D: int, mspec, want_lat: bool, size: int,
          block: int, start_tick, sh: Dict, rep: Dict):
    """The per-shard program: local init, the elected-winner scan, and the
    post-scan reductions that make every output replicated."""
    from repro.core.replay import metrics as _metrics

    H, O = cfg.num_hosts, cfg.outstanding
    Hl = H // D
    L = sh["addrs"].shape[1]
    MH = cfg.max_hops
    me = jax.lax.axis_index("hosts")
    addrs_l, writes_l, lens_l = sh["addrs"], sh["writes"], sh["lens"]

    st0 = stack.init_state(cfg.stack, Hl)
    aux0 = {}
    if mspec is not None:
        # replicated-*shaped*, locally accumulated: each shard adds only
        # its owner-steps, the post-scan psum folds them to global totals
        aux0["acc"] = jnp.zeros(
            (_metrics.acc_rows(mspec, H, cfg.num_devs), 4), jnp.int64)
        aux0["med"] = jnp.zeros(
            (cfg.num_devs, len(_metrics.MEDIA_COUNTERS[cfg.stack.kind])),
            jnp.int64)
        aux0["q"] = jnp.zeros(cfg.num_ports, jnp.int64)
        if cfg.qos:
            aux0["qthr"] = jnp.zeros(cfg.num_ports, jnp.int64)
        fc0 = stack.flash_counters(st0)
        if fc0 is not None:
            aux0["flash"] = fc0                     # local (Hl, 5) snapshot
        if cfg.stack.faults:
            aux0["faults"] = jnp.stack(stack.fault_counters(st0))
    if not want_lat:
        aux0["first"] = jnp.full(Hl, BIG, jnp.int64)
        aux0["last"] = jnp.full(Hl, start_tick, jnp.int64)
        aux0["sum"] = jnp.zeros(Hl, jnp.int64)
        aux0["cnt"] = jnp.zeros(Hl, jnp.int64)
        aux0["bad"] = jnp.zeros((), bool)
        aux0["gcs"] = _i64(0)
    init = (jnp.full((Hl, O), start_tick, jnp.int64),
            jnp.full(Hl, start_tick, jnp.int64),
            jnp.zeros(Hl, jnp.int64),
            jnp.zeros(cfg.num_ports, jnp.int64),
            _i64(1),
            st0,
            jnp.zeros((cfg.num_ports, H), jnp.int64),
            jnp.full((cfg.num_ports, H), NEVER, jnp.int64),
            aux0)

    def step(carry, _):
        slots, now, idx, port_busy, ctr, st, vft, last_arr, aux = carry
        # -- collective 1: winner election (global lowest-(tick, index))
        with jax.named_scope("lfb"):
            cand = jnp.where(idx < lens_l,
                             jnp.maximum(now, jnp.min(slots, axis=1)), BIG)
            li0 = jnp.argmin(cand)
        with jax.named_scope("collective"):
            g = jax.lax.all_gather(
                jnp.stack([cand[li0], li0.astype(jnp.int64)]), "hosts")
        with jax.named_scope("lfb"):
            w = jnp.argmin(g[:, 0])        # ties -> lowest shard
            li = g[w, 1]                   # winner's local lane (owner shard)
            issue = g[w, 0]                # == max(now, min slot) when valid
            valid = issue < BIG
            am = me == w
            gate = am & valid
            i_glob = w * Hl + li
        # -- collective 2: the owner's access record, broadcast to all
        ix = jnp.clip(idx[li], 0, L - 1)
        a0 = addrs_l[li, ix]
        w0 = writes_l[li, ix].astype(jnp.int64)
        if cfg.fault_hops:
            on_v = sh["fhon"][li, ix].astype(jnp.int64)
            pi_v = sh["fhp"][li, ix].astype(jnp.int64)
            occ_v = sh["fho"][li, ix]
            aft_v = sh["fha"][li, ix]
            occc_v = sh["fhoc"][li, ix]
        else:
            r = sh["route"][li, ix] if cfg.max_routes > 1 else 0
            on_v = sh["hop_on"][li, r].astype(jnp.int64)
            pi_v = sh["hop_port"][li, r].astype(jnp.int64)
            occ_v = sh["hop_occ"][li, r]
            aft_v = sh["hop_after"][li, r]
            occc_v = occ_v
        rec = jnp.concatenate([jnp.stack([a0, w0]), on_v, pi_v, occ_v,
                               aft_v, occc_v])
        with jax.named_scope("collective"):
            rec = jax.lax.psum(jnp.where(gate, rec, 0), "hosts")
        a = rec[0]
        wr = rec[1] > 0
        posted = wr if cfg.posted_writes else jnp.zeros((), bool)
        # -- replicated transport walk + QoS mirror (identical on every
        # shard: broadcast inputs, replicated state — byte-for-byte the
        # unsharded loop, reading the record instead of the lookup)
        with jax.named_scope("transport"):
            t = jnp.where(valid, issue, _i64(0))
            floor = _i64(0)
            qacc = aux.get("q")
            qthr = aux.get("qthr")
            for h in range(MH):
                on = rec[2 + h] > 0
                pi = rec[2 + MH + h]
                occ_h = rec[2 + 2 * MH + h]
                aft_h = rec[2 + 3 * MH + h]
                occ_c = rec[2 + 4 * MH + h]
                if cfg.qos:
                    qon = on & rep["qos_on"][pi]
                    prev = vft[pi, i_glob]
                    win = occ_c * ACTIVE_WINDOW_OCC
                    w_active = jnp.float64(0.0)
                    # sorted-name order, like the dict walk
                    for j in cfg.host_order:
                        member = (j == i_glob) | (last_arr[pi, j] + win > t)
                        w_active = w_active + jnp.where(
                            member, rep["qos_w"][pi, j], 0.0)
                    pace = (occ_c.astype(jnp.float64)
                            * (w_active / rep["qos_w"][pi, i_glob])
                            ).astype(jnp.int64)
                    floor = jnp.maximum(
                        floor, jnp.where(qon & (prev > t), prev + pace, 0))
                    vft = vft.at[pi, i_glob].set(
                        jnp.where(qon, jnp.maximum(prev, t) + pace, prev))
                    last_arr = last_arr.at[pi, i_glob].set(
                        jnp.where(qon, t, last_arr[pi, i_glob]))
                    if qthr is not None:
                        qthr = qthr.at[pi].add(
                            jnp.where(qon & (prev > t) & valid, 1, 0))
                start = jnp.maximum(t, port_busy[pi])
                if qacc is not None:
                    qacc = qacc.at[pi].add(
                        jnp.where(on & valid, start - t, 0))
                done_h = start + occ_h
                port_busy = port_busy.at[pi].set(
                    jnp.where(on, done_h, port_busy[pi]))
                t = jnp.where(on, done_h + aft_h, t)
            t = t + rep["rt_extra"]
        # -- SPMD media step: every shard runs it on lane `li` of its own
        # local state, only the owner commits (en gate); non-owner outputs
        # are garbage and every use below is owner-gated
        with jax.named_scope("media"):
            if cfg.stack.kind == DRAM:
                p_med = {"occ": rep["dev_occ"][i_glob],
                         "load": rep["dev_load"][i_glob],
                         "pack": rep["dev_pack"][i_glob]}
            else:
                p_med = rep
        st, out = stack.step(cfg.stack, p_med, st, dict(
            lane=li, flash_lane=li, t=t, addr=a, write=wr, posted=posted,
            ctr=ctr, en=gate))
        done = out["done"]
        if cfg.qos:
            with jax.named_scope("transport"):
                done = jnp.maximum(done, floor)
        with jax.named_scope("telemetry"):
            bad_l, gcs_l = stack.flash_health(st)
            if mspec is not None:
                aux = {**aux,
                       "acc": _metrics.acc_update(
                           mspec, aux["acc"], host=i_glob, dev=i_glob,
                           n_hosts=H, n_devs=cfg.num_devs, issue=issue,
                           done=done,
                           size=size, hit=out["hit"], valid=gate),
                       "med": aux["med"].at[i_glob].add(
                           _metrics.media_increments(cfg.stack.kind, wr, out)
                           * jnp.where(gate, 1, 0)),
                       "q": qacc}
                if qthr is not None:
                    aux = {**aux, "qthr": qthr}
                if "flash" in aux:
                    aux = {**aux, "flash": jnp.where(
                        valid, stack.flash_counters(st), aux["flash"])}
                if "faults" in aux:
                    aux = {**aux, "faults": jnp.where(
                        valid, jnp.stack(stack.fault_counters(st)),
                        aux["faults"])}
            if not want_lat:
                aux = {**aux,
                       "first": aux["first"].at[li].min(
                           jnp.where(gate, issue, BIG)),
                       "last": aux["last"].at[li].max(
                           jnp.where(gate, done, _i64(-BIG))),
                       "sum": aux["sum"].at[li].add(
                           jnp.where(gate, done - issue, 0)),
                       "cnt": aux["cnt"].at[li].add(jnp.where(gate, 1, 0)),
                       "bad": aux["bad"] | (bad_l & valid),
                       "gcs": jnp.where(valid, gcs_l, aux["gcs"])}
        with jax.named_scope("lfb"):
            k = jnp.argmin(slots[li])
            slots = slots.at[li, k].set(jnp.where(gate, done, slots[li, k]))
            now = now.at[li].set(
                jnp.where(gate, issue + rep["issue_ov"], now[li]))
            idx = idx.at[li].set(jnp.where(gate, idx[li] + 1, idx[li]))
        ys = ((i_glob, issue, jnp.where(gate, done, 0),
               jnp.where(bad_l, 1, 0), gcs_l) if want_lat else None)
        return ((slots, now, idx, port_busy, ctr + 1, st, vft, last_arr,
                 aux), ys)

    carry, ys = jax.lax.scan(step, init, None, length=H * L, unroll=block)
    aux = carry[8]
    # -- post-scan reductions: every returned leaf becomes replicated
    with jax.named_scope("collective"):
        if want_lat:
            who, issues, d_gated, bad_i, gcs_loc = ys
            dones = jax.lax.psum(d_gated, "hosts")
            bad = jax.lax.psum(bad_i, "hosts") > 0
            gcs = jax.lax.psum(gcs_loc, "hosts")
        else:
            who = issues = dones = bad = gcs = None
        if mspec is not None:
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
            for k in _FAULT_KEYS:
                sh[k] = params[k]
        else:
            diag = np.arange(H)
            for k in ("hop_port", "hop_occ", "hop_after", "hop_on"):
                sh[k] = np.ascontiguousarray(params[k][diag, diag])
            if cfg.max_routes > 1:
                sh["route"] = params["route"]
        skip = {"hop_port", "hop_occ", "hop_after", "hop_on", "route",
                "flash_of", *_FAULT_KEYS}
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
