"""Fused multi-host replay: N hosts interleaved in one :func:`jax.lax.scan`.

The scan reproduces :class:`repro.core.workloads.driver.MultiHostDriver`'s
global issue ordering exactly: each step selects the host with the earliest
candidate issue tick (``max(own clock, own oldest LFB slot)``, ties to the
lowest host index — the heap's ``(tick, index)`` order), pops that host's
next access, walks its precomputed route over the *shared* per-port
busy-until vector, and serializes on the target device's media state.
Contention between hosts therefore emerges from the same shared state as in
the interpreted driver, tick for tick.

The device media is the stackable state layer of
:mod:`repro.core.replay.stack`: one private media lane per mounted device
(per host in mount mode, per pool device in pool mode) over zero or more
flash instances — so the full cached-CXL-SSD stack replays fused, including
the pooled-flash shape (per-host private DRAM caches sharing one FTL/PAL
flash array, built by handing several :class:`CachedCXLSSDDevice` front
ends one ``hil=``) and greedy FTL garbage collection.

QoS and ECMP are mirrored operation-for-operation:

* **ECMP** — the per-access route choice is precomputed host-side with the
  same :func:`~repro.core.fabric.routing.flow_choices` hash the interpreted
  path evaluates per access, and the hop tensors gain a route axis.
* **QoS** — per-port per-host virtual-finish-time and last-arrival carries
  replicate :meth:`SwitchPort.qos_update`: the weight sum runs over hosts
  in sorted-name order (the same float64 add order as the Python ``dict``
  walk), the pace uses the identical ``int(occ * (W / w))`` truncation, and
  the resulting floor binds the final host acknowledgment only — the
  physical port walk is untouched, exactly like the interpreted path.

Supported targets (homogeneous): :class:`FabricAttachedDevice` mounts and
:class:`HostPortView` pool views over any media the stack layer models —
DRAM-class (heterogeneous timing allowed), PMEM, CXL-SSD, cached CXL-SSD
(lru/fifo/direct, identical configuration across targets).  The pool's
address mapper is applied host-side (it is a pure function of the address),
so interleave and segment modes cost nothing in the scan.  A pool's
logical-device partitions are applied there too: each view's LD base is
added, and an access outside its LD is refused with the interpreted view's
error before anything compiles.  Anything else raises
:class:`ReplayUnsupported` naming the widest lane that still covers the
shape (the ``engine='python'`` fallback) — lanes refuse, they never
silently diverge.

Transport faults (link CRC-retry bursts, port/link down windows with ECMP
exclusion and failover reroutes, poison status) mirror tick-identically on
per-host mounts: every (host, ordinal) pair walks the same pure
:meth:`Fabric.select_faulted` route selection the interpreted mount
performs — keyed on that host's own access ordinal, exactly the per-mount
``_fault_ord`` counter — and the hop columns ride per-access ``(H, L,
max_hops)`` tensors with CRC retries pre-charged into the physical
occupancy while the QoS virtual clock paces on the clean column.  Pool
views with link/down faults refuse (interleaving scrambles the per-host
fault ordinals); an unreachable down segment raises
:class:`~repro.core.faults.DeviceUnreachable` at prepare, matching the
first access the python driver would fail on.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import namedtuple
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64
from jax.profiler import TraceAnnotation, annotate_function

from repro.core.devices import (CXLDRAMDevice, DRAMDevice, NullLink,
                                POSTED_ACK_NS)
from repro.core.engine import ns
from repro.core.fabric.fabric import LINE_BYTES, Fabric, FabricAttachedDevice
from repro.core.fabric.pool import HostPortView, ld_range_error, ld_table
from repro.core.fabric.routing import flow_choices, flow_hash
from repro.core.fabric.switch import ACTIVE_WINDOW_OCC
from repro.core.replay import stack
from repro.core.replay.spec import (DRAM, ReplayUnsupported, StackConfig,
                                    compact_pages, media_stack,
                                    trace_to_arrays,
                                    validate_block_size,
                                    validate_trace_columns)
from repro.core.replay.stack import MAX_ACCESSES, _i64
from repro.core.workloads.driver import MultiHostResult, TraceResult
from repro.obs import scopes

BIG = 1 << 62
# "never arrived" sentinel for the QoS last-arrival carry: far enough below
# zero that sentinel + activity window can never exceed a valid tick.
NEVER = -(1 << 61)
# the per-access transport hop columns of an active fault plan: port,
# charged occupancy, after, on-mask, clean occupancy
FAULT_KEYS = ("fhp", "fho", "fha", "fhon", "fhoc")


@dataclass(frozen=True)
class MultiCfg:
    num_hosts: int
    outstanding: int
    posted_writes: bool
    num_ports: int
    max_hops: int
    num_devs: int
    stack: StackConfig           # media/flash statics (transportless)
    n_flash: int = 0             # flash instances (0 for flash-less media)
    max_routes: int = 1
    qos: bool = False
    # host indices in sorted-host-name order: the QoS weight sum must add
    # floats in exactly the order SwitchPort.qos_update's sorted() walk does
    host_order: Tuple[int, ...] = ()
    # transport faults active: hop columns ride per-access (H, L, max_hops)
    # tensors instead of the static per-(host, dev, route) hop tensors
    fault_hops: bool = False


def _port_index(fabric: Fabric) -> Dict[Tuple[str, str], int]:
    return {key: i for i, key in enumerate(sorted(fabric.ports))}


def _route_rows(fabric: Fabric, host: str, node: str, size: int,
                pidx: Dict[Tuple[str, str], int], max_hops: int,
                choice: int):
    hops = fabric.route_occupancy(host, node, size, choice=choice)
    if len(hops) > max_hops:
        raise AssertionError("max_hops underestimated")
    port = np.zeros(max_hops, np.int32)
    occ = np.zeros(max_hops, np.int64)
    after = np.zeros(max_hops, np.int64)
    on = np.zeros(max_hops, bool)
    for h, (key, occ_h, after_h) in enumerate(hops):
        port[h] = pidx[key]
        occ[h] = occ_h
        after[h] = after_h
        on[h] = True
    return port, occ, after, on


def _extract_targets(targets: Sequence, size: int):
    """Shared fabric + route/QoS tensors and metadata for mounts or pool
    views (the media half is extracted separately by :func:`_media_setup`,
    which needs the mapped address range)."""
    first = targets[0]
    lds = None
    if isinstance(first, FabricAttachedDevice):
        fabric = first.fabric
        if not all(isinstance(t, FabricAttachedDevice)
                   and t.fabric is fabric for t in targets):
            raise ReplayUnsupported("hosts must share one fabric")
        hosts = [t.host for t in targets]
        nodes = [t.device_node for t in targets]
        inners = [t.inner for t in targets]
        dev_of = {n: i for i, n in enumerate(nodes)}
        if len(dev_of) != len(nodes):
            raise ReplayUnsupported(
                "fused mount mode needs one private device per host "
                "(share devices through a MemoryPool instead)")
        mapper = None
    elif isinstance(first, HostPortView):
        pool = first.pool
        if not all(isinstance(t, HostPortView) and t.pool is pool
                   for t in targets):
            raise ReplayUnsupported("pool views must share one MemoryPool")
        fabric = pool.fabric
        hosts = [t.host for t in targets]
        nodes = pool.device_nodes
        inners = list(pool.devices)
        mapper = pool.mapper
        lds = ld_table(targets)
    else:
        raise ReplayUnsupported(
            f"multi-host fused replay supports FabricAttachedDevice / "
            f"HostPortView targets, got {type(first).__name__}; "
            "use engine='python'")
    for t in list(targets) + inners:
        if t.stats.get("bytes", 0):
            raise ReplayUnsupported("targets must be fresh (no prior traffic)")
    if fabric.stats.get("transfers", 0):
        raise ReplayUnsupported(
            "fabric has prior traffic; replay snapshots a fresh fabric "
            "(Fabric.reset() or re-build it, or use engine='python')")
    qos = fabric.qos_enabled
    if qos and len(set(hosts)) != len(hosts):
        raise ReplayUnsupported(
            "QoS arbitration keys per-origin state by host name; give each "
            "host view a distinct host node (or use engine='python')")
    plan = getattr(fabric, "fault_plan", None)
    if plan is None:
        plan = next((q for q in (getattr(t, "fault_plan", None)
                                 for t in targets) if q is not None), None)
    if plan is not None and not plan.active:
        plan = None
    if (plan is not None and (plan.has_link or plan.has_down)
            and mapper is not None):
        raise ReplayUnsupported(
            f"multi-host fused replay mirrors transport faults "
            f"({', '.join(plan.class_names())}) on per-host fabric mounts "
            "only — pool address interleaving scrambles the per-host fault "
            "ordinals; use engine='python' for faulted pools")

    pidx = _port_index(fabric)
    pairs = ([(i, i) for i in range(len(hosts))] if mapper is None else
             [(i, d) for i in range(len(hosts)) for d in range(len(nodes))])
    max_hops = max(fabric.routing.hops(hosts[i], nodes[d]) for i, d in pairs)
    H, NDEV = len(hosts), len(nodes)
    route_count = np.ones((H, NDEV), np.int32)
    for i, d in pairs:
        route_count[i, d] = len(fabric.paths(hosts[i], nodes[d]))
    K = int(route_count.max())
    hop_port = np.zeros((H, NDEV, K, max_hops), np.int32)
    hop_occ = np.zeros((H, NDEV, K, max_hops), np.int64)
    hop_after = np.zeros((H, NDEV, K, max_hops), np.int64)
    hop_on = np.zeros((H, NDEV, K, max_hops), bool)
    for i, h in enumerate(hosts):
        for d, n in enumerate(nodes):
            if mapper is None and d != i:
                continue        # mount mode: host i only reaches device i
            for k in range(route_count[i, d]):
                (hop_port[i, d, k], hop_occ[i, d, k], hop_after[i, d, k],
                 hop_on[i, d, k]) = _route_rows(fabric, h, n, size, pidx,
                                                max_hops, k)
    params = {
        "hop_port": hop_port, "hop_occ": hop_occ, "hop_after": hop_after,
        "hop_on": hop_on,
        "rt_extra": ns(fabric.rt_extra_ns),
    }
    host_order: Tuple[int, ...] = ()
    if qos:
        ports_sorted = sorted(fabric.ports)
        params["qos_on"] = np.asarray(
            [fabric.ports[key].qos_enabled for key in ports_sorted], bool)
        params["qos_w"] = np.asarray(
            [[fabric.ports[key].weight_of(hname) for hname in hosts]
             for key in ports_sorted], np.float64)
        host_order = tuple(int(j) for j in
                           sorted(range(H), key=lambda j: hosts[j]))
    # transport faults ride the fabric: the interpreted mount passes an
    # ordinal into traverse_qos only when the plan sits on the *fabric*
    # (FabricAttachedDevice.service checks fabric.fault_plan), so the
    # fused columns key on exactly that
    fab_plan = getattr(fabric, "fault_plan", None)
    if fab_plan is not None and not fab_plan.active:
        fab_plan = None
    transport_plan = (fab_plan if fab_plan is not None
                      and (fab_plan.has_link or fab_plan.has_down) else None)
    meta = dict(fabric=fabric, mapper=mapper, lds=lds, hosts=hosts,
                nodes=nodes, inners=inners, route_count=route_count, qos=qos,
                host_order=host_order, num_ports=len(pidx),
                max_hops=max_hops, max_routes=K, num_devs=NDEV,
                fault_plan=plan, transport_plan=transport_plan)
    return params, meta


def _dram_class(dev):
    """Bare DRAM, or CXL-DRAM whose private link the fabric mount
    neutralized (the only shapes with per-device timing arrays)."""
    if isinstance(dev, DRAMDevice):
        return dev
    if isinstance(dev, CXLDRAMDevice) and isinstance(dev.link, NullLink):
        return dev.dram
    return None


def _params_equal(a: Dict, b: Dict) -> bool:
    return set(a) == set(b) and all(np.array_equal(a[k], b[k]) for k in a)


def _media_setup(inners: Sequence, *, size: int, outstanding: int,
                 posted_writes: bool, n_accesses: int, max_addr: int,
                 counters: bool = False):
    """The media half of the multi-host stack: one
    :class:`~repro.core.replay.spec.StackConfig` shared by every target,
    media timing params, and the media-lane -> flash-instance map (deduped
    by the backing :class:`HIL` object, so front ends built over one shared
    ``hil=`` contend on one flash state — exactly like the interpreted
    path).  Heterogeneous timing is allowed for DRAM-class media (per-device
    arrays); every other kind must be identically configured."""
    specs = [media_stack(d, size=size, outstanding=outstanding,
                         posted_writes=posted_writes, n_accesses=n_accesses,
                         max_addr=max_addr, counters=counters)
             for d in inners]
    cfg0, mp0 = specs[0]
    for k, (cfgk, mpk) in enumerate(specs[1:], start=1):
        if cfgk != cfg0 or (cfg0.kind != DRAM
                            and not _params_equal(mpk, mp0)):
            raise ReplayUnsupported(
                f"multi-host targets must be identically configured "
                f"({cfg0.kind!r} media differs at target {k}); "
                "use engine='python'")
    if cfg0.kind == DRAM:
        drams = [_dram_class(d) for d in inners]
        media_params = {
            "dev_occ": np.asarray([ns(size / d.t.bw_gbps) for d in drams],
                                  np.int64),
            "dev_load": np.asarray([ns(d.t.load_ns) for d in drams],
                                   np.int64),
            "dev_pack": np.asarray([ns(POSTED_ACK_NS)] * len(drams),
                                   np.int64),
        }
        return cfg0, media_params, np.zeros(len(inners), np.int32), 0
    if not stack.has_flash(cfg0):
        return cfg0, mp0, np.zeros(len(inners), np.int32), 0
    flash_lane: Dict[int, int] = {}
    flash_of = np.zeros(len(inners), np.int32)
    for i, d in enumerate(inners):
        flash_of[i] = flash_lane.setdefault(id(d.hil), len(flash_lane))
    return cfg0, mp0, flash_of, len(flash_lane)


def _multi_init(cfg: MultiCfg, start_tick, mspec=None,
                want_lat: bool = True, local: Optional[int] = None):
    """The full multi-host carry pytree at ``start_tick`` — per-host LFB
    slots / clocks / trace cursors, shared port busy-untils, stamp counter,
    stacked media/flash state, the QoS virtual-finish / last-arrival
    tables, and the aux accumulators.  Built eagerly by the chunked driver
    (buffer-donated across chunk calls) and traced by :func:`_run_multi`;
    identical structure either way, which is what makes chunked multi-host
    replay tick-identical to one-shot.  With metrics, the histogram/window
    accumulator and the media counter vector ride the carry only in
    streaming mode (``want_lat=False``): with per-access streams they are
    folded on the host from the streams and the flags column.  ``local``
    is a shard's host count: its rows, media lanes and private flashes,
    beside global port, QoS and metrics tables."""
    H, O = cfg.num_hosts, cfg.outstanding
    hosts, lanes, n_flash = ((H, cfg.num_devs, cfg.n_flash or None)
                             if local is None else (local, local, local))
    state0 = stack.init_state(cfg.stack, lanes, n_flash)
    aux0 = {}
    if mspec is not None:
        from repro.core.replay import metrics as _metrics
        if not want_lat:
            aux0["acc"] = jnp.zeros(
                (_metrics.acc_rows(mspec, H, cfg.num_devs), 4), jnp.int64)
            aux0["med"] = jnp.zeros(
                (cfg.num_devs, len(_metrics.MEDIA_COUNTERS[cfg.stack.kind])),
                jnp.int64)
        aux0["q"] = jnp.zeros(cfg.num_ports, jnp.int64)
        if cfg.qos:
            aux0["qthr"] = jnp.zeros(cfg.num_ports, jnp.int64)
        fc0 = stack.flash_counters(state0)
        if fc0 is not None:
            # snapshot carry: padded steps are strictly trailing, so the
            # last *valid* snapshot is the true end-of-trace total
            aux0["flash"] = fc0
        if cfg.stack.faults:
            aux0["faults"] = jnp.stack(stack.fault_counters(state0))
    if not want_lat:
        aux0["first"] = jnp.full(hosts, BIG, jnp.int64)
        aux0["last"] = jnp.full(hosts, start_tick, jnp.int64)
        aux0["sum"] = jnp.zeros(hosts, jnp.int64)
        aux0["cnt"] = jnp.zeros(hosts, jnp.int64)
        aux0["bad"] = jnp.zeros((), bool)
        aux0["gcs"] = _i64(0)
    return (jnp.full((hosts, O), start_tick, jnp.int64),  # LFB slots
            jnp.full(hosts, start_tick, jnp.int64),       # issue clocks
            jnp.zeros(hosts, jnp.int64),                  # trace indices
            jnp.zeros(cfg.num_ports, jnp.int64),       # shared port busy
            _i64(1),                                   # global stamp counter
            # stacked media/flash state: one lane per mounted device
            state0,
            # QoS: per-port per-host virtual finish + last arrival
            jnp.zeros((cfg.num_ports, H), jnp.int64),
            jnp.full((cfg.num_ports, H), NEVER, jnp.int64),
            aux0)


# What a lane's pick hands the multi-host step: the issuing ``host``'s
# global index (QoS tables, metrics, ``who``) and its ``row`` in the carry's
# slots/now/idx, the LFB ``slot`` it reuses, ``issue`` and ``valid``, the
# access (``addr``, ``write``), its global ``dev`` (DRAM timing, metrics)
# and media/flash lanes, ``hop(h) -> (on, port, charged occupancy, clean
# occupancy, after)``, and ``own``, the gate on the carry's commits
# (``None`` where the lane holds every host).
Pick = namedtuple("Pick", "host row slot issue valid addr write dev lane "
                  "flash_lane hop own", defaults=(None,))


def _local_pick(cfg: MultiCfg, p: Dict, lens, cols: Dict, base=None):
    """The pick of a lane that holds every host: the earliest candidate
    host (``max(own clock, oldest LFB slot)``, ties to the lowest index),
    its access read from ``cols`` (``addr``/``wr``/``dev``, plus ``route``
    and the :data:`FAULT_KEYS` hop columns where ``cfg`` has them): the
    full padded ``(H, L)`` trace arrays (one-shot), or per-host ``(H, S)``
    windows whose row ``i`` starts at trace index ``base[i]`` (chunked)."""

    def pick(slots, now, idx):
        with jax.named_scope("lfb"):
            cand = jnp.where(idx < lens,
                             jnp.maximum(now, jnp.min(slots, axis=1)), BIG)
            i = jnp.argmin(cand)                 # ties -> lowest host index
            valid = idx[i] < lens[i]             # padded steps are trailing
            row = slots[i]
            k = jnp.argmin(row)
            issue = jnp.maximum(now[i], row[k])
        ix = idx[i]
        if base is not None:
            ix = jnp.clip(ix - base[i], 0, cols["addr"].shape[1] - 1)
        r = cols["route"][i, ix] if cfg.max_routes > 1 else 0
        fc = ({c: cols[c][i, ix] for c in FAULT_KEYS} if cfg.fault_hops
              else None)
        a, wr, dev = cols["addr"][i, ix], cols["wr"][i, ix], cols["dev"][i, ix]

        def hop(h):
            if fc is not None:
                # retries charged: occ * (1 + r); clean: the QoS entitlement
                return (fc["fhon"][h], fc["fhp"][h], fc["fho"][h],
                        fc["fhoc"][h], fc["fha"][h])
            on = p["hop_on"][i, dev, r, h]
            pi = p["hop_port"][i, dev, r, h]
            occ = p["hop_occ"][i, dev, r, h]
            return on, pi, occ, occ, p["hop_after"][i, dev, r, h]

        return Pick(host=i, row=i, slot=k, issue=issue, valid=valid, addr=a,
                    write=wr, dev=dev, lane=dev,
                    flash_lane=(p["flash_of"][dev] if cfg.n_flash else 0),
                    hop=hop)

    return pick


def _make_multi_step(cfg: MultiCfg, p: Dict, pick, mspec=None,
                     want_lat: bool = True, size: int = 64):
    """The per-step body of every multi-host scan.  ``pick(slots, now,
    idx) -> Pick`` is the lane's seam: which host issues next and what its
    access is (:func:`_local_pick`, or the sharded lane's election and
    broadcast).  The step walks the pick's hops over the shared port
    busy-untils — the QoS mirror pacing on the *clean* occupancy while the
    physical busy-until charges retries, exactly like
    ``SwitchPort.qos_update`` + ``transmit(retries=...)`` — runs
    :func:`stack.step` on its lanes and commits the LFB, all gated by
    ``own`` where the pick gives one.  With ``want_lat`` each step emits
    ``(host, issue, done, bad, gcs)``, and the int32 flags word
    (:func:`~repro.core.replay.metrics.event_flags`) when metrics are
    folded from those streams."""
    H = cfg.num_hosts

    def step(carry, _):
        slots, now, idx, port_busy, ctr, st, vft, last_arr, aux = carry
        pk = pick(slots, now, idx)
        i, issue, valid, wr = pk.host, pk.issue, pk.valid, pk.write
        gate = valid if pk.own is None else pk.own
        posted = wr if cfg.posted_writes else jnp.zeros((), bool)
        with jax.named_scope("transport"):
            t = issue
            floor = _i64(0)
            qacc = aux.get("q")
            qthr = aux.get("qthr")
            for h in range(cfg.max_hops):
                on, pi, occ_h, occ_c, after_h = pk.hop(h)
                if cfg.qos:
                    # mirror of SwitchPort.qos_update at arrival tick t
                    qon = on & p["qos_on"][pi]
                    prev = vft[pi, i]
                    win = occ_c * ACTIVE_WINDOW_OCC
                    w_active = jnp.float64(0.0)
                    # sorted-name order, like the dict walk
                    for j in cfg.host_order:
                        member = (j == i) | (last_arr[pi, j] + win > t)
                        w_active = w_active + jnp.where(
                            member, p["qos_w"][pi, j], 0.0)
                    pace = (occ_c.astype(jnp.float64)
                            * (w_active / p["qos_w"][pi, i])
                            ).astype(jnp.int64)
                    floor = jnp.maximum(
                        floor, jnp.where(qon & (prev > t), prev + pace, 0))
                    vft = vft.at[pi, i].set(
                        jnp.where(qon, jnp.maximum(prev, t) + pace, prev))
                    last_arr = last_arr.at[pi, i].set(
                        jnp.where(qon, t, last_arr[pi, i]))
                    if qthr is not None:
                        # SwitchPort.qos_update's nonzero-floor return is the
                        # python qos_throttle_events bump, hop for hop
                        qthr = qthr.at[pi].add(
                            jnp.where(qon & (prev > t) & valid, 1, 0))
                start = jnp.maximum(t, port_busy[pi])
                if qacc is not None:
                    # SwitchPort.transmit: queued_ticks += start - now
                    qacc = qacc.at[pi].add(
                        jnp.where(on & valid, start - t, 0))
                done_h = start + occ_h
                port_busy = port_busy.at[pi].set(
                    jnp.where(on, done_h, port_busy[pi]))
                t = jnp.where(on, done_h + after_h, t)
            t = t + p["rt_extra"]
        dev = pk.dev
        with jax.named_scope("media"):
            if cfg.stack.kind == DRAM:
                # DRAM-class media keeps per-device timing arrays
                # (heterogeneous pools); the stack step reads its scalar
                # names
                p_med = {"occ": p["dev_occ"][dev],
                         "load": p["dev_load"][dev],
                         "pack": p["dev_pack"][dev]}
            else:
                p_med = p
        access = dict(lane=pk.lane, flash_lane=pk.flash_lane, t=t,
                      addr=pk.addr, write=wr, posted=posted, ctr=ctr)
        if pk.own is not None:
            access["en"] = pk.own
        st, out = stack.step(cfg.stack, p_med, st, access)
        done = out["done"]
        if cfg.qos:
            with jax.named_scope("transport"):
                # ack floor, data path untouched
                done = jnp.maximum(done, floor)
        flags = None
        row = pk.row
        with jax.named_scope("telemetry"):
            bad, gcs = stack.flash_health(st)
            if mspec is not None:
                from repro.core.replay import metrics as _metrics
                aux = {**aux, "q": qacc}
                if "acc" in aux:
                    aux["acc"] = _metrics.acc_update(
                        mspec, aux["acc"], host=i, dev=dev, n_hosts=H,
                        n_devs=cfg.num_devs, issue=issue, done=done,
                        size=size, hit=out["hit"], valid=gate)
                    aux["med"] = aux["med"].at[dev].add(
                        _metrics.media_increments(cfg.stack.kind, wr, out)
                        * jnp.where(gate, 1, 0))
                else:
                    flags = _metrics.event_flags(cfg.stack.kind, out)
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
                neg = _i64(-BIG)
                aux = {**aux,
                       "first": aux["first"].at[row].min(
                           jnp.where(gate, issue, BIG)),
                       "last": aux["last"].at[row].max(
                           jnp.where(gate, done, neg)),
                       "sum": aux["sum"].at[row].add(
                           jnp.where(gate, done - issue, 0)),
                       "cnt": aux["cnt"].at[row].add(jnp.where(gate, 1, 0)),
                       "bad": aux["bad"] | (bad & valid),
                       "gcs": jnp.where(valid, gcs, aux["gcs"])}
        with jax.named_scope("lfb"):
            k = pk.slot
            if pk.own is None:
                slots = slots.at[row, k].set(done)
                now = now.at[row].set(issue + p["issue_ov"])
                idx = idx.at[row].set(idx[row] + 1)
            else:
                own = pk.own
                slots = slots.at[row, k].set(
                    jnp.where(own, done, slots[row, k]))
                now = now.at[row].set(
                    jnp.where(own, issue + p["issue_ov"], now[row]))
                idx = idx.at[row].set(jnp.where(own, idx[row] + 1, idx[row]))
        ys = None
        if want_lat:
            ys = (i, issue, done, bad, gcs)
            if flags is not None:
                ys = ys + (flags,)
        return ((slots, now, idx, port_busy, ctr + 1, st, vft, last_arr,
                 aux), ys)

    return step


@functools.partial(jax.jit, static_argnums=(0, 7, 8, 9, 10))
def _run_multi(cfg: MultiCfg, p: Dict, devs, addrs, writes, lens, start_tick,
               block: int = 1, mspec=None, want_lat: bool = True,
               size: int = 64):
    init = _multi_init(cfg, start_tick, mspec, want_lat)
    cols = {**p, "addr": addrs, "wr": writes, "dev": devs}
    step = _make_multi_step(cfg, p, _local_pick(cfg, p, lens, cols),
                            mspec, want_lat, size)
    # Blocked replay: `block` steps per sequential scan iteration (unroll).
    # The carry — including the per-host candidate race state (slots, now,
    # idx) — crosses block seams untouched, so the earliest-candidate-host
    # selection and its lowest-index tie-break behave identically whether a
    # tie lands mid-block or exactly on a seam (regression-tested).
    n_total = addrs.shape[0] * addrs.shape[1]
    carry, ys = jax.lax.scan(step, init, None, length=n_total, unroll=block)
    return _split_ys(ys, carry[8])


def _split_ys(ys, aux):
    """``(who, issues, dones, bad, gcs, aux)`` from the scan's per-step
    columns; the flags column of a deferred metrics fold rides ``aux``
    under ``"flags"``, beside the carry's accumulators."""
    if ys is None:
        return None, None, None, None, None, aux
    if len(ys) > 5:
        aux = {**aux, "flags": ys[5]}
    return (*ys[:5], aux)


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9),
                   donate_argnums=(1,))
def _run_multi_chunk(cfg: MultiCfg, carry, p: Dict, wins: Dict, lens, base,
                     block: int = 1, mspec=None, want_lat: bool = True,
                     size: int = 64):
    """One jitted window of the chunked multi-host replay: ``S`` scan steps
    over per-host ``(H, S)`` trace windows, each window starting at that
    host's ``base`` cursor.  Every step consumes at most one access from
    exactly one host, so ``S`` steps can never outrun an ``S``-wide
    window; trailing padded reads (an exhausted host re-picked once all
    candidates hit the sentinel) clip into the window and are discarded by
    the same validity gates as the one-shot path.  The carry is donated —
    threading state across an arbitrarily long trace allocates O(window),
    not O(trace)."""
    step = _make_multi_step(cfg, p, _local_pick(cfg, p, lens, wins, base),
                            mspec, want_lat, size)
    return jax.lax.scan(step, carry, None, length=wins["addr"].shape[1],
                        unroll=block)


def _map_addrs(mapper, host_idx: int, addrs: np.ndarray, ld=None,
               host: str = "", size: int = LINE_BYTES):
    """Host-side pool address mapping (pure per-address arithmetic).
    ``ld`` is the view's :func:`~repro.core.fabric.pool.ld_table` entry:
    an access outside the LD raises :meth:`HostPortView.pool_address`'s
    error, else the LD base is added before the mapper."""
    if mapper is None:
        return np.full(addrs.shape, host_idx, np.int32), addrs
    if ld is not None:
        out = (addrs < 0) | (addrs + size > ld["bytes"])
        if out.any():
            raise ld_range_error(host, ld["ld"], int(addrs[np.argmax(out)]),
                                 size, ld["bytes"])
        addrs = addrs + ld["base"]
    if mapper.mode == "interleave":
        frame, off = np.divmod(addrs, mapper.granularity)
        dev = (frame % mapper.num_devices).astype(np.int32)
        local = (frame // mapper.num_devices) * mapper.granularity + off
        return dev, local
    dev64, local = np.divmod(addrs, mapper.segment_bytes)
    if (dev64 >= mapper.num_devices).any():
        raise ReplayUnsupported("address beyond pool capacity")
    return dev64.astype(np.int32), local


def _compact_prepared(prep):
    """A prepared tuple as the device runs it: the address column in dense
    page ids over every host's valid entries and the stack config with the
    map sized to them (:func:`~repro.core.replay.spec.compact_pages`).
    :meth:`MultiHostReplay.prepare_arrays` keeps the device-local
    addresses and the span-sized map, for callers that read them."""
    cfg, params, devs, addrs, writes, lens, size = prep
    valid = np.arange(addrs.shape[1]) < np.asarray(lens)[:, None]
    st, addrs = compact_pages(cfg.stack, addrs, valid)
    return (dataclasses.replace(cfg, stack=st), params, devs, addrs, writes,
            lens, size)


# -------------------------------------------------- transport fault columns
def _fault_cols_multi(meta: Dict, plan, addrs: np.ndarray,
                      lens: np.ndarray, size: int):
    """Per-host per-access transport hop columns under the installed
    link-retry / down-window plan — the multi-host twin of the single-host
    :class:`~repro.core.replay.engine._FaultColumnBuilder`, with the host
    axis and the *global* sorted-port index (so the shared ``port_busy`` /
    QoS ``vft``/``last_arr`` carries and the ``qos_on``/``qos_w`` params
    keep their indexing untouched).

    Every (host, ordinal) walks the same pure route selection the
    interpreted mount performs (:meth:`Fabric.select_faulted`, keyed on
    that host's *own* access ordinal — the per-mount ``_fault_ord``
    counter) and the same per-hop occupancy rule, pre-charging CRC-retry
    serializations into the occupancy column; the clean occupancy rides a
    separate column for the QoS virtual clock.  Raises
    :class:`~repro.core.faults.DeviceUnreachable` at precompute for the
    same segments the python driver would fail on.

    Returns ``(cols, num_hops, faulted, fstats, deg, fo)``: the five
    ``(H, L, num_hops)`` hop columns, the widest (failover-inclusive) hop
    count, the accumulated per-port/per-host/ECMP totals for
    :func:`~repro.core.replay.metrics.bundle_multi_fused`'s ``faulted=``
    override, the shared fault-counter totals, and per-host ``(H, L)``
    degraded/failover availability flags."""
    fab = meta["fabric"]
    hosts, nodes = meta["hosts"], meta["nodes"]
    pidx = _port_index(fab)
    P = len(pidx)
    H, L = addrs.shape
    lens = np.asarray(lens, np.int64)
    # candidate path set per host: one entry per distinct down segment —
    # the route chosen for an ordinal depends only on its segment's down
    # set and the flow hash, never on the ordinal itself
    occ_of: List[Dict[Tuple[str, ...], list]] = [dict() for _ in range(H)]
    for i in range(H):
        n_i = int(lens[i])
        if not n_i:
            continue
        segs = (plan.down_segments(n_i) if plan.has_down
                else [(0, n_i, frozenset())])
        for _, _, down in segs:
            ps = fab.routing.paths(hosts[i], nodes[i], down=down)
            for q in (ps if fab.ecmp else [ps[0]]):
                key = tuple(q)
                if key not in occ_of[i]:
                    occ_of[i][key] = fab.path_occupancy(q, size)
    FH = max((len(hops) for d in occ_of for hops in d.values()), default=1)
    fhp = np.zeros((H, L, FH), np.int32)
    fho = np.zeros((H, L, FH), np.int64)
    fha = np.zeros((H, L, FH), np.int64)
    fhon = np.zeros((H, L, FH), bool)
    fhoc = np.zeros((H, L, FH), np.int64)
    deg = np.zeros((H, L), bool)
    fo = np.zeros((H, L), bool)
    pkts = np.zeros(P, np.int64)
    occt = np.zeros(P, np.int64)
    by_host = np.zeros((P, H), np.int64)
    ecmp: Dict[str, List[int]] = {}
    link_retries = failovers = degraded = 0
    for i in range(H):
        host, node = hosts[i], nodes[i]
        K = len(fab.paths(host, node))
        for j in range(int(lens[i])):
            line_addr = int(addrs[i, j]) // LINE_BYTES
            path, dg, fv = fab.select_faulted(host, node, line_addr, j)
            if dg:
                deg[i, j] = True
                degraded += 1
                if fv:
                    fo[i, j] = True
                    failovers += 1
            elif fab.ecmp and K > 1:
                # mirror traverse_qos: clean ECMP choices still count
                k = flow_hash(host, node, line_addr) % K
                ecmp.setdefault(f"{host}->{node}", [0] * K)[k] += 1
            for h, (pk, occ, after) in enumerate(occ_of[i][tuple(path)]):
                rt = plan.link_retries(pk, j) if plan.has_link else 0
                link_retries += rt
                pi = pidx[pk]
                fhp[i, j, h] = pi
                fho[i, j, h] = occ * (1 + rt)
                fha[i, j, h] = after
                fhon[i, j, h] = True
                fhoc[i, j, h] = occ
                pkts[pi] += 1
                occt[pi] += occ * (1 + rt)
                by_host[pi, i] += size    # goodput: retries move 0 bytes
    faulted = {"port_keys": sorted(fab.ports), "packets": pkts,
               "bytes": pkts * size, "occupied": occt, "by_host": by_host,
               "ecmp": ecmp}
    fstats = {"link_retries": int(link_retries),
              "failovers": int(failovers),
              "degraded_accesses": int(degraded)}
    cols = {"fhp": fhp, "fho": fho, "fha": fha, "fhon": fhon, "fhoc": fhoc}
    return cols, FH, faulted, fstats, deg, fo


class MultiHostReplay:
    """Fused, vectorized stand-in for :class:`MultiHostDriver` (pooled or
    per-host fabric targets over any stack-layer media — DRAM-class, PMEM,
    CXL-SSD, cached CXL-SSD with private or shared flash — QoS weights,
    ECMP, and greedy FTL GC included).  ``run`` is tick-identical to the
    interpreted driver for supported shapes."""

    def __init__(self, targets: Sequence, outstanding: int = 32,
                 issue_overhead_ns: float = 0.5,
                 posted_writes: bool = True, block_size: int = 1,
                 metrics=None) -> None:
        if not targets:
            raise ReplayUnsupported("need at least one host target")
        self.targets = list(targets)
        self.outstanding = max(1, outstanding)
        self.issue_overhead_ns = issue_overhead_ns
        self.posted_writes = posted_writes
        self.block_size = validate_block_size(block_size)
        self.last_gc_runs = 0    # flash GC collections in the last run
        self.metrics = metrics   # Optional[MetricsSpec]
        self.last_metrics = None  # MetricsBundle of the last run
        self._meta = None

    def prepare(self, traces: Sequence):
        """Extract (cfg, params, devs, addrs, writes, lens, size) tensors —
        the compiled program's inputs.  Exposed so sweeps can batch them.
        Per-access route choices ride inside ``params["route"]``."""
        if len(traces) != len(self.targets):
            raise ValueError(f"{len(traces)} traces for "
                             f"{len(self.targets)} host targets")
        parsed = [trace_to_arrays(tr) for tr in traces]
        size = parsed[0][2]
        if any(pz != size for _, _, pz in parsed):
            raise ReplayUnsupported("hosts must share one access size")
        H = len(self.targets)
        L = max(a.size for a, _, _ in parsed)
        addrs = np.zeros((H, L), np.int64)
        writes = np.zeros((H, L), bool)
        lens = np.asarray([a.size for a, _, _ in parsed], np.int64)
        for i, (a, w, _) in enumerate(parsed):
            addrs[i, :a.size] = a
            writes[i, :a.size] = w
        return self.prepare_arrays(addrs, writes, lens=lens, size=size)

    @functools.partial(annotate_function, name="replay.build")
    def prepare_arrays(self, addrs, writes, *, lens=None, size: int = 64):
        """:meth:`prepare` for traces that already live as ``(H, L)``
        columns — on-device workload synthesis (:mod:`repro.data.workloads`)
        or :class:`~repro.data.trace_store.TraceStore` loads — so fleet-scale
        inputs never round-trip through per-access python tuples.  Pool
        address mapping and ECMP route-choice hashing stay host-side
        numpy column ops (pure per-address arithmetic, bit-equal to the
        per-access scalar path)."""
        addrs, writes, lens = validate_trace_columns(
            addrs, writes, lens, size=size)
        H, L = addrs.shape
        if H != len(self.targets):
            raise ValueError(f"{H} trace rows for "
                             f"{len(self.targets)} host targets")
        params, meta = _extract_targets(self.targets, size)
        self._meta = meta        # labels/fabric for metrics bundle assembly
        devs = np.zeros((H, L), np.int32)
        routes = np.zeros((H, L), np.int32)
        mapper, route_count = meta["mapper"], meta["route_count"]
        tplan = meta["transport_plan"]
        lds = meta["lds"]
        if mapper is not None:
            addrs = addrs.copy()    # mapping rewrites to device-local addrs
        with TraceAnnotation("pool.map"):
            for i in range(H):
                n = int(lens[i])
                dev, local = _map_addrs(mapper, i, addrs[i, :n],
                                        lds[i] if lds else None,
                                        meta["hosts"][i], size)
                addrs[i, :n] = local
                devs[i, :n] = dev
                if meta["max_routes"] > 1 and tplan is None:
                    # same hash, same flow key (device-local line address)
                    # as HostPortView / FabricAttachedDevice evaluate per
                    # access
                    for d in np.unique(dev):
                        m = dev == d
                        routes[i, :n][m] = flow_choices(
                            meta["hosts"][i], meta["nodes"][d],
                            local[m] // LINE_BYTES, int(route_count[i, d]))
        stack_cfg, media_params, flash_of, n_flash = _media_setup(
            meta["inners"], size=size, outstanding=self.outstanding,
            posted_writes=self.posted_writes, n_accesses=int(lens.sum()),
            max_addr=int(addrs.max(initial=0)),
            counters=self.metrics is not None)
        if stack.has_flash(stack_cfg) and H * L > MAX_ACCESSES:
            raise ReplayUnsupported(
                f"multi-host SSD replay of {H}x{L} steps exceeds the "
                f"packed-stamp budget ({MAX_ACCESSES}); split the traces "
                "or use engine='python'")
        params.update(media_params)
        params["flash_of"] = flash_of
        params["issue_ov"] = ns(self.issue_overhead_ns)
        params["route"] = routes
        max_hops, max_routes = meta["max_hops"], meta["max_routes"]
        if tplan is not None:
            # link-retry / down-window columns: per-access hop tensors
            # replace the static per-(host, dev, route) ones; the ECMP
            # choice (over survivors) is baked into the columns, so the
            # route axis collapses
            fcols, fh, faulted, fstats, degf, fof = _fault_cols_multi(
                meta, tplan, addrs, lens, size)
            params.update(fcols)
            meta["faulted"] = faulted
            meta["fault_stats"] = fstats
            meta["deg_flags"] = degf
            meta["fo_flags"] = fof
            max_hops, max_routes = fh, 1
        # poison status parity: the driver tallies each target plan's
        # deterministic (host, ordinal) poison flags on the service path
        poisoned = 0
        for i, tgt in enumerate(self.targets):
            tp = getattr(tgt, "fault_plan", None)
            if tp is not None and tp.has_poison:
                n_i = int(lens[i])
                poisoned += int(tp.poisoned_np(
                    i, np.arange(n_i, dtype=np.int64),
                    writes[i, :n_i]).sum())
        meta["poisoned_reads"] = poisoned
        cfg = MultiCfg(num_hosts=H, outstanding=self.outstanding,
                       posted_writes=self.posted_writes,
                       num_ports=meta["num_ports"],
                       max_hops=max_hops, num_devs=meta["num_devs"],
                       stack=stack_cfg, n_flash=n_flash,
                       max_routes=max_routes, qos=meta["qos"],
                       host_order=meta["host_order"],
                       fault_hops=tplan is not None)
        return cfg, params, devs, addrs, writes, lens, size

    @property
    def fault_flags(self):
        """Per-host ``(degraded, failover)`` flag arrays (each ``(H, L)``
        bool) from the last :meth:`prepare` under an active transport
        plan, else ``None`` — the availability-sweep lane folds these into
        reachable-fraction / time-in-degraded curves."""
        if self._meta is None or "deg_flags" not in self._meta:
            return None
        return self._meta["deg_flags"], self._meta["fo_flags"]

    @staticmethod
    @functools.partial(annotate_function, name="replay.finish")
    def aggregate(who, issues, dones, lens, size: int,
                  start_tick: int = 0) -> MultiHostResult:
        """Fold per-step (host, issue, done) streams into per-host results.

        Padded steps beyond sum(lens) pick exhausted hosts (cand == BIG);
        they replay "past the end" deterministically but must be dropped."""
        who = np.asarray(who)
        issues = np.asarray(issues)
        dones = np.asarray(dones)
        lens = np.asarray(lens)
        valid = np.arange(who.size) < int(lens.sum())
        per_host: List[TraceResult] = []
        firsts, lasts = [], []
        for i in range(lens.size):
            m = valid & (who == i)
            iss, dn = issues[m], dones[m]
            n = int(m.sum())
            first = int(iss[0]) if n else None
            last = max(int(dn.max(initial=0)), start_tick) if n else start_tick
            per_host.append(TraceResult(
                accesses=n, bytes_moved=n * size,
                elapsed_ticks=(last - first) if first is not None else 0,
                sum_latency_ticks=int((dn - iss).sum()),
                end_tick=last))
            if first is not None:
                firsts.append(first)
            lasts.append(last)
        first_all = min(firsts, default=start_tick)
        return MultiHostResult(per_host=per_host,
                               elapsed_ticks=max(lasts) - first_all)

    @staticmethod
    def _aggregate_scalars(aux, lens, size: int,
                           start_tick: int = 0) -> MultiHostResult:
        """The ``return_latencies=False`` twin of :meth:`aggregate`: fold
        the in-scan per-host first/last/sum/count scalars (O(hosts) output,
        never O(trace)) into the same result shape."""
        firsts = np.asarray(aux["first"])
        lasts = np.asarray(aux["last"])
        sums = np.asarray(aux["sum"])
        cnts = np.asarray(aux["cnt"])
        lens = np.asarray(lens)
        per_host: List[TraceResult] = []
        first_list, last_list = [], []
        for i in range(lens.size):
            n = int(cnts[i])
            first = int(firsts[i]) if n else None
            last = max(int(lasts[i]), start_tick) if n else start_tick
            per_host.append(TraceResult(
                accesses=n, bytes_moved=n * size,
                elapsed_ticks=(last - first) if first is not None else 0,
                sum_latency_ticks=int(sums[i]),
                end_tick=last))
            if first is not None:
                first_list.append(first)
            last_list.append(last)
        first_all = min(first_list, default=start_tick)
        return MultiHostResult(per_host=per_host,
                               elapsed_ticks=max(last_list) - first_all)

    def _run_chunked(self, cfg, params, devs, addrs, writes, lens,
                     start_tick, mspec, want_lat, size, chunk):
        """Chunked multi-host replay: the scan consumes per-host sliding
        windows of ``chunk`` accesses, re-sliced host-side from each
        host's carry cursor after every window (each step consumes at most
        one access, so a ``chunk``-wide window per host can never be
        outrun).  The carry — the shared port busy-untils, QoS
        virtual-finish/last-arrival tables, media/flash state and metrics
        accumulators — is buffer-donated across windows; the windows are
        contiguous slices, so feeding them from memmapped columns keeps
        peak input residency O(hosts * chunk).  Tick-identical to the
        one-shot scan: both run the same step body over the same access
        sequence, only the lookup re-bases."""
        from repro.core.replay.engine import _dealias

        if chunk < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk!r}")
        routes = params["route"]
        fcols = ({k: params[k] for k in FAULT_KEYS} if cfg.fault_hops
                 else None)
        skip = {"route", *FAULT_KEYS}
        pj = jax.tree.map(jnp.asarray,
                          {k: v for k, v in params.items() if k not in skip})
        lens_np = np.asarray(lens, np.int64)
        lj = jnp.asarray(lens_np)
        H = cfg.num_hosts
        total = int(lens_np.sum())
        carry = _multi_init(cfg, _i64(start_tick), mspec, want_lat)
        parts = []
        n_calls = max(1, -(-total // chunk))
        for _ in range(n_calls):
            base = np.minimum(np.asarray(carry[2], np.int64), lens_np)
            wa = np.zeros((H, chunk), np.int64)
            ww = np.zeros((H, chunk), bool)
            wd = np.zeros((H, chunk), np.int32)
            wr_ = np.zeros((H, chunk), np.int32)
            wf = ({k: np.zeros((H, chunk) + v.shape[2:], v.dtype)
                   for k, v in fcols.items()} if fcols is not None else None)
            for i in range(H):
                b = int(base[i])
                e = min(b + chunk, int(lens_np[i]))
                if e > b:
                    wa[i, :e - b] = addrs[i, b:e]
                    ww[i, :e - b] = writes[i, b:e]
                    wd[i, :e - b] = devs[i, b:e]
                    if cfg.max_routes > 1:
                        wr_[i, :e - b] = routes[i, b:e]
                    if wf is not None:
                        for k, v in fcols.items():
                            wf[k][i, :e - b] = v[i, b:e]
            wins = {"addr": jnp.asarray(wa), "wr": jnp.asarray(ww),
                    "dev": jnp.asarray(wd)}
            if cfg.max_routes > 1:
                wins["route"] = jnp.asarray(wr_)
            if wf is not None:
                wins.update({k: jnp.asarray(v) for k, v in wf.items()})
            carry, ys = scopes.run(
                _run_multi_chunk, cfg, _dealias(carry), pj, wins, lj,
                jnp.asarray(base), self.block_size, mspec, want_lat, size)
            if want_lat:
                parts.append(tuple(np.asarray(y) for y in ys))
        ys = (tuple(np.concatenate([pt[j] for pt in parts])
                    for j in range(len(parts[0]))) if want_lat else None)
        return _split_ys(ys, carry[8])

    def _execute(self, traces: Sequence, start_tick: int,
                 want_lat: bool = True, chunk_size=None):
        return self._execute_prepared(self.prepare(traces), start_tick,
                                      want_lat, chunk_size)

    def _dispatch(self, cfg, params, devs, addrs, writes, lens, start_tick,
                  mspec, want_lat, size, chunk_size):
        """The raw compiled-run dispatch (called under ``enable_x64``) —
        the single override point for lanes that run the same prepared
        tensors through a different program (the sharded fleet lane)."""
        if chunk_size is not None:
            return self._run_chunked(
                cfg, params, devs, addrs, writes, lens, start_tick,
                mspec, want_lat, size, int(chunk_size))
        with TraceAnnotation("replay.put"):
            args = (cfg, jax.tree.map(jnp.asarray, params), jnp.asarray(devs),
                    jnp.asarray(addrs), jnp.asarray(writes),
                    jnp.asarray(lens), _i64(start_tick))
        with TraceAnnotation("replay.run"):
            return scopes.run(_run_multi, *args, self.block_size, mspec,
                              want_lat, size)

    def _execute_prepared(self, prep, start_tick: int,
                          want_lat: bool = True, chunk_size=None):
        cfg, params, devs, addrs, writes, lens, size = _compact_prepared(prep)
        if cfg.qos and start_tick < 0:
            raise ReplayUnsupported(
                "QoS replay needs start_tick >= 0 (the virtual-clock and "
                "arrival sentinels assume non-negative ticks)")
        mspec = self.metrics
        with enable_x64(True):
            who, issues, dones, bad, gcs, aux = self._dispatch(
                cfg, params, devs, addrs, writes, lens, start_tick,
                mspec, want_lat, size, chunk_size)
            if want_lat:
                with TraceAnnotation("replay.fetch"):
                    bad = np.asarray(bad)
                    gcs = np.asarray(gcs)
                    who, issues, dones = (np.asarray(who), np.asarray(issues),
                                          np.asarray(dones))
                    if "flags" in aux:
                        aux = {**aux, "flags": np.asarray(aux["flags"])}
        # padded steps (beyond sum(lens)) replay past the end and may dirty
        # the sticky flash flags — judge health at the last *valid* step
        total = int(np.asarray(lens).sum())
        if want_lat:
            self.last_gc_runs = int(gcs[total - 1]) if total else 0
            bad_last = bool(bad[total - 1]) if total else False
        else:
            self.last_gc_runs = int(aux["gcs"]) if total else 0
            bad_last = bool(aux["bad"]) if total else False
        if bad_last:
            raise ReplayUnsupported(
                "FTL ran out of free blocks during GC (device overfilled) — "
                "the interpreted path raises there too; shrink the traces "
                "or use engine='python' for the exact error")
        bundle = None
        if mspec is not None:
            from repro.core.replay import metrics as _metrics
            fcnt = (np.asarray(aux["flash"]) if "flash" in aux else None)
            fdict = None
            if (self._meta.get("fault_plan") is not None
                    or self._meta.get("poisoned_reads")):
                rr, rb = (np.asarray(aux["faults"]) if "faults" in aux
                          else (0, 0))
                fs = self._meta.get("fault_stats") or {}
                fdict = {"link_retries": fs.get("link_retries", 0),
                         "failovers": fs.get("failovers", 0),
                         "degraded_accesses": fs.get("degraded_accesses", 0),
                         "nand_read_retries": int(rr),
                         "retired_blocks": int(rb),
                         "poisoned_reads":
                             int(self._meta.get("poisoned_reads", 0))}
            if not want_lat:
                # streaming mode: the scan carried the accumulator
                bundle = _metrics.bundle_multi_fused(
                    mspec, self._meta, cfg, aux["acc"], aux["med"], aux["q"],
                    aux.get("qthr"), fcnt, devs, params["route"], lens, size,
                    params, faults=fdict, faulted=self._meta.get("faulted"))
            else:
                bundle = _metrics.bundle_multi_deferred(
                    mspec, self._meta, cfg, who, issues, dones, aux["flags"],
                    writes, aux["q"], aux.get("qthr"), fcnt, devs,
                    params["route"], lens, size, params, faults=fdict,
                    faulted=self._meta.get("faulted"))
        self.last_metrics = bundle
        return who, issues, dones, lens, size, aux, bundle

    @staticmethod
    def _attach(res: MultiHostResult, bundle) -> MultiHostResult:
        if bundle is not None:
            res.metrics = bundle
            for r in res.per_host:
                r.metrics = bundle
        return res

    def run(self, traces: Sequence, start_tick: int = 0,
            return_latencies: bool = True,
            chunk_size=None) -> MultiHostResult:
        return self._run(self.prepare(traces), start_tick, return_latencies,
                         chunk_size)

    def run_arrays(self, addrs, writes, *, lens=None, size: int = 64,
                   start_tick: int = 0, return_latencies: bool = True,
                   chunk_size=None) -> MultiHostResult:
        """:meth:`run` over already-columnar ``(H, L)`` trace arrays (see
        :meth:`prepare_arrays`) — the fleet-scale entry point: synthesized
        or store-loaded traces replay without ever materializing python
        tuple lists."""
        return self._run(self.prepare_arrays(addrs, writes, lens=lens,
                                             size=size),
                         start_tick, return_latencies, chunk_size)

    def _run(self, prep, start_tick, return_latencies, chunk_size):
        who, issues, dones, lens, size, aux, bundle = self._execute_prepared(
            prep, start_tick, want_lat=bool(return_latencies),
            chunk_size=chunk_size)
        if return_latencies:
            res = self.aggregate(who, issues, dones, lens, size, start_tick)
        else:
            res = self._aggregate_scalars(aux, lens, size, start_tick)
        return self._attach(res, bundle)

    def run_recorded(self, traces: Sequence, start_tick: int = 0,
                     chunk_size=None
                     ) -> Tuple[MultiHostResult, List[np.ndarray]]:
        """:meth:`run` plus the per-access latency stream of every host
        (in that host's issue order) — tensors the scan already produced
        for free, exposed for conformance pinning and tail analysis."""
        who, issues, dones, lens, size, aux, bundle = self._execute(
            traces, start_tick, chunk_size=chunk_size)
        res = self.aggregate(who, issues, dones, lens, size, start_tick)
        valid = np.arange(who.size) < int(np.asarray(lens).sum())
        lat = [(dones - issues)[valid & (who == i)]
               for i in range(len(self.targets))]
        return self._attach(res, bundle), lat
