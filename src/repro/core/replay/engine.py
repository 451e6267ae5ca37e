"""Fused device-stack trace replay in a single :func:`jax.lax.scan`.

One scan step = one trace access, end to end: LFB slot recycling (the
driver's bounded-outstanding issue model), link/fabric transport with
per-port busy-until occupancy, then the device media — DRAM timing, PMEM
row-buffer, the CXL-SSD page-register buffer, or the full DRAM-cache layer
(fully-associative LRU/FIFO or direct-mapped frames, MSHR coalescing and
stalls, bounded writeback buffer) backed by the HIL/FTL/PAL flash model
(log-append allocation with greedy garbage collection when the trace can
outrun the headroom, per-die array occupancy with program suspend,
per-channel bus occupancy).

The stateful media/flash machinery lives in :mod:`repro.core.replay.stack`
— the host-stackable state layer this engine consumes at ``H=1`` and
:class:`~repro.core.replay.multihost.MultiHostReplay` consumes at ``H=N``.
The step function mirrors the interpreted path *operation for operation* —
every ``max(now, busy_until)``, every separately-rounded ``ns()`` constant —
so the replay is **tick-identical** to
:meth:`repro.core.workloads.driver.TraceDriver.run` over the same device
(property-tested in ``tests/test_replay.py``).  Scope cuts are host-checked
at spec time so they can never silently diverge (one 64 B line per access,
packed-field ranges); runtime-only divergence (a GC free-pool underrun,
where the interpreted FTL raises "out of space") surfaces as
:class:`ReplayUnsupported` via the stack's sticky ``bad`` flag — refuse,
never drift.

Streaming: the scan body is factored so the same compiled chunk program
can either consume the whole trace in one call (the legacy one-shot path)
or be driven by an outer chunk loop that threads the full carry pytree —
LFB slots, issue clock, port busy-untils, stacked media/flash state and
the metrics accumulators — across chunk boundaries with buffer donation
(:func:`_chunked_scan`).  Peak *input* residency is then O(chunk) instead
of O(trace); pair with ``return_latencies=False`` (PR 6's streaming
accumulators) for O(chunk) end to end.  ``ReplayEngine.run_store`` replays
straight from an on-disk columnar :class:`~repro.data.trace_store.TraceStore`
without ever materializing the trace.

Performance notes (XLA:CPU executes a scan body as a sequence of fusion
thunks, so the step is written to minimize thunks and buffer copies):

* cache frames live in ONE packed int64 per frame —
  ``stamp<<39 | page<<1 | dirty`` — so residency is one fused
  compare+argmax, the LRU/FIFO victim is one plain ``argmin`` (invalid
  frames are -1, below every packed value), and each access commits exactly
  one scatter;
* the entire miss machinery (MSHR allocate/stall, eviction writeback queue,
  FTL/PAL flash timing) sits behind one :func:`jax.lax.cond`, which
  passes the big carry buffers through untouched on hits — and the greedy-GC
  migration loop sits behind a second cond inside that one;
* MSHR/writeback tables use value sentinels (page ``-1`` = free slot,
  ready ``BIG``) instead of separate mask arrays;
* transport port busy-until state is a tuple of scalars (hop *h* always
  uses port *h* on a single-host route), fusing into neighboring
  elementwise work.

Tick arithmetic runs in int64 under :func:`jax.enable_x64`
(scoped — the rest of the process keeps JAX's default 32-bit types; the
golden suite also runs under ambient ``JAX_ENABLE_X64=1`` in CI to guard
both entry modes); at 1 tick = 1 ps, int32 would overflow after 2.1 ms of
simulated time.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64
from jax.profiler import TraceAnnotation, annotate_function

from repro.core.fabric.fabric import FabricAttachedDevice
from repro.core.replay import stack
from repro.core.replay.spec import (
    ReplayUnsupported,
    StackConfig,
    build_stack,
    compact_pages,
    trace_to_arrays,
    validate_block_size,
)
# The packed-frame layout and sentinels are owned by the stack layer now;
# importers take them from repro.core.replay.stack directly.
from repro.core.replay.stack import BIG, MAX_ACCESSES, _i64
from repro.core.workloads.driver import TraceResult
from repro.obs import scopes


def _qos_mask(cfg: StackConfig):
    """Boolean constant over the busy-until vector: which ports run
    weighted QoS arbitration (from the static ``cfg.qos_ports``)."""
    m = np.zeros(cfg.num_ports, bool)
    if cfg.qos_ports:
        m[list(cfg.qos_ports)] = True
    return jnp.asarray(m)


# ---------------------------------------------------------------- transport
@scopes.scoped("transport")
def _transport(cfg: StackConfig, p: Dict, pb: Tuple, t, qacc=None, qthr=None):
    """Routed store-and-forward transport: the vectorized form of
    :meth:`SwitchPort.transmit` along the precomputed route (hop *h* is
    port *h*), plus the CXL.mem round-trip extra.  ``qacc`` (optional, a
    tuple like ``pb``) accumulates per-port queueing — the
    ``queued_ticks += start - now`` of :meth:`SwitchPort.transmit` — for
    the metrics carry.

    ``qthr`` (optional, same container) accumulates the per-port
    ``qos_throttle_events`` twin of :meth:`SwitchPort.qos_update` on the
    hops ``cfg.qos_ports`` marks as weighted: with a single origin the
    pace equals the clean occupancy exactly, so the virtual finish time
    obeys the *same* recurrence as the port's busy-until
    (``max(state, t) + occ`` from 0) and ``pb[h]`` at arrival IS the
    origin's virtual finish — the counter bumps exactly when the
    interpreted ``prev > now`` does, with no extra carry.  (The ack floor
    provably never binds for one origin — see
    :func:`repro.core.replay.spec._fabric_hops` — so only the counter
    needs mirroring.)"""
    pb = list(pb)
    q = list(qacc) if qacc is not None else None
    qt = list(qthr) if qthr is not None else None
    for h in range(cfg.num_hops):
        if qt is not None and h in cfg.qos_ports:
            qt[h] = qt[h] + jnp.where(pb[h] > t, 1, 0)
        start = jnp.maximum(t, pb[h])
        if q is not None:
            q[h] = q[h] + (start - t)
        done = start + p["hop_occ"][h]
        pb[h] = done
        t = done + p["hop_after"][h]
    return (tuple(pb), t + p["rt_extra"],
            tuple(q) if q is not None else None,
            tuple(qt) if qt is not None else None)


@scopes.scoped("transport")
def _transport_cols(cfg: StackConfig, p: Dict, pb, t, cols, qacc=None,
                    vft=None, qthr=None):
    """Fault-lane transport: each access carries its own hop columns
    (precomputed host-side under the installed
    :class:`~repro.core.faults.FaultPlan`) — port index, occupancy with
    CRC retries already charged (``occ * (1 + retries)``), store-and-forward
    extra, an on-mask padding shorter routes up to the widest failover
    route, and the retry-free *clean* occupancy.  Off hops are no-ops on
    every piece of state, so mixed hop counts (down-window reroutes onto
    longer paths) stay exact.  ``pb`` is the port busy-until vector over
    the union of ports any access touches.

    ``vft``/``qthr`` mirror :meth:`SwitchPort.qos_update` on the weighted
    union ports: CRC retries stretch the port's serialization but never
    the origin's entitlement, so the virtual clock advances by the clean
    occupancy column and needs its own carry here — the busy-until
    recurrence identity the retry-free lanes exploit breaks once
    ``occ * (1 + retries)`` and the clean pace diverge."""
    hop_port, hop_occ, hop_after, hop_on, hop_clean = cols
    qmask = _qos_mask(cfg) if qthr is not None else None
    for h in range(cfg.num_hops):
        on = hop_on[h]
        pi = hop_port[h]
        if qthr is not None:
            qon = on & qmask[pi]
            prev = vft[pi]
            qthr = qthr.at[pi].add(jnp.where(qon & (prev > t), 1, 0))
            vft = vft.at[pi].set(
                jnp.where(qon, jnp.maximum(prev, t) + hop_clean[h], prev))
        start = jnp.maximum(t, pb[pi])
        if qacc is not None:
            qacc = qacc.at[pi].add(jnp.where(on, start - t, 0))
        done = start + hop_occ[h]
        pb = pb.at[pi].set(jnp.where(on, done, pb[pi]))
        t = jnp.where(on, done + hop_after[h], t)
    return pb, t + p["rt_extra"], qacc, vft, qthr


@scopes.scoped("transport")
def _transport_ecmp(cfg: StackConfig, p: Dict, pb, t, route, qacc=None,
                    qthr=None):
    """ECMP transport: hop *h* of the chosen route occupies the port
    ``hop_port[route, h]`` of the path set's port union, so the busy-until
    state is a vector indexed per access instead of a positional tuple.
    All equal-cost routes share one hop count (static).  ``qacc``
    (optional, a vector like ``pb``) accumulates per-port queueing;
    ``qthr`` mirrors the per-port QoS throttle counter on the weighted
    union ports — ``pb[pi]`` at arrival doubles as the origin's virtual
    finish time, exactly as in :func:`_transport`."""
    qmask = _qos_mask(cfg) if qthr is not None else None
    for h in range(cfg.num_hops):
        pi = p["hop_port"][route, h]
        if qthr is not None:
            qthr = qthr.at[pi].add(jnp.where(qmask[pi] & (pb[pi] > t), 1, 0))
        start = jnp.maximum(t, pb[pi])
        if qacc is not None:
            qacc = qacc.at[pi].add(start - t)
        done = start + p["hop_occ"][route, h]
        pb = pb.at[pi].set(done)
        t = done + p["hop_after"][route, h]
    return pb, t + p["rt_extra"], qacc, qthr


# ---------------------------------------------------------- fault columns
class _FaultColumnBuilder:
    """Per-access transport hop columns for a fabric mount under an active
    link-retry / down-window plan, producible one contiguous ordinal range
    at a time.

    Every access ordinal walks the *same* pure route selection the
    interpreted path uses (:meth:`Fabric.select_faulted` — degraded-set
    masking, ECMP over survivors, recomputed fallback routes) and the same
    per-hop occupancy rule (:meth:`Fabric.path_occupancy`), pre-charging
    CRC-retry serializations into the occupancy column; the clean (retry-
    free) occupancy rides its own column for the QoS virtual clock.
    Raises :class:`~repro.core.faults.DeviceUnreachable` for the same
    accesses the python driver would — at construction, since the plan's
    down segments already determine which route sets go empty.

    The static shapes — the port union and the hop width ``num_hops`` —
    are derived from the plan's :meth:`~FaultPlan.down_segments` alone
    (route sets depend on the down set, never on the address), so columns
    for any ordinal range compute without seeing the rest of the trace.
    That is what lets transport faults *stream*: ``run_store`` builds
    columns chunk by chunk, and the accumulated port/ECMP/counter totals
    round-trip through :meth:`state`/:meth:`load_state` so a checkpointed
    run resumes mid-trace bit-exactly."""

    def __init__(self, device, plan, size: int, n: int,
                 keep_flags: bool = True) -> None:
        from repro.core.devices import CXLDRAMDevice
        from repro.core.replay.spec import _link_hops

        self.fab = device.fabric
        self.plan = plan
        self.host, self.node = device.host, device.device_node
        self.size = int(size)
        self.n = int(n)
        self.keep_flags = keep_flags
        fab = self.fab
        segs = (plan.down_segments(self.n) if plan.has_down
                else [(0, self.n, frozenset())])
        # union of every path any ordinal can take: per down segment, the
        # surviving (ECMP) set — or its recomputed failover routes — which
        # is exactly the candidate set select_faulted chooses from.  An
        # all-paths-down segment raises DeviceUnreachable here, matching
        # the first access the interpreted driver would fail on.
        self._occ: Dict[Tuple[str, ...], list] = {}
        for _, _, down in segs:
            ps = fab.routing.paths(self.host, self.node, down=down)
            for q in (ps if fab.ecmp else [ps[0]]):
                key = tuple(q)
                if key not in self._occ:
                    self._occ[key] = fab.path_occupancy(q, self.size)
        self.K = len(fab.paths(self.host, self.node))
        # a fabric-mounted CXL-DRAM kept on its private link
        # (detach_link=False) pays one extra uncontended transport stage
        # after the fabric — same append build_stack does for the clean
        # route tensors
        self._ih: list = []
        if isinstance(device.inner, CXLDRAMDevice):
            self._ih, _ = _link_hops(device.inner.link, self.size)
        self.port_keys = sorted({pk for hops in self._occ.values()
                                 for pk, _, _ in hops})
        self._pidx = {k: i for i, k in enumerate(self.port_keys)}
        base = len(self.port_keys)
        self.num_hops = (max(len(h) for h in self._occ.values())
                         + (1 if self._ih else 0))
        self.num_ports = base + (1 if self._ih else 0)
        self._pkts = np.zeros(max(base, 1), np.int64)
        self._occt = np.zeros(max(base, 1), np.int64)
        self._ecmp: Dict[str, List[int]] = {}
        self._link_retries = 0
        self._failovers = 0
        self._degraded = 0
        self._deg_parts: List[np.ndarray] = []
        self._fo_parts: List[np.ndarray] = []

    def columns(self, addrs: np.ndarray, lo: int) -> Dict[str, np.ndarray]:
        """Hop columns for ordinals ``[lo, lo + len(addrs))``; updates the
        running port/ECMP/counter totals and (when ``keep_flags``) the
        per-access degraded/failover availability flags."""
        from repro.core.fabric.fabric import LINE_BYTES
        from repro.core.fabric.routing import flow_hash

        fab, plan = self.fab, self.plan
        host, node = self.host, self.node
        addrs = np.asarray(addrs, np.int64)
        m = int(addrs.size)
        H = self.num_hops
        P = len(self.port_keys)
        hp = np.zeros((m, H), np.int32)
        ho = np.zeros((m, H), np.int64)
        ha = np.zeros((m, H), np.int64)
        hon = np.zeros((m, H), bool)
        hoc = np.zeros((m, H), np.int64)
        deg = np.zeros(m, bool)
        fo = np.zeros(m, bool)
        for r in range(m):
            j = lo + r
            line_addr = int(addrs[r]) // LINE_BYTES
            path, dg, fv = fab.select_faulted(host, node, line_addr, j)
            if dg:
                deg[r] = True
                self._degraded += 1
                if fv:
                    fo[r] = True
                    self._failovers += 1
            elif fab.ecmp and self.K > 1:
                # mirror traverse_qos: clean ECMP choices still count
                k = flow_hash(host, node, line_addr) % self.K
                counts = self._ecmp.setdefault(f"{host}->{node}",
                                               [0] * self.K)
                counts[k] += 1
            for h, (pk, occ, after) in enumerate(self._occ[tuple(path)]):
                rt = plan.link_retries(pk, j) if plan.has_link else 0
                self._link_retries += rt
                i = self._pidx[pk]
                hp[r, h] = i
                ho[r, h] = occ * (1 + rt)
                ha[r, h] = after
                hon[r, h] = True
                hoc[r, h] = occ
                self._pkts[i] += 1
                self._occt[i] += occ * (1 + rt)
            if self._ih:
                # off-hops between row end and H-1 are no-ops, so the
                # private hop sits at the fixed last column for every access
                hp[r, H - 1] = P
                ho[r, H - 1] = self._ih[0][1]
                ha[r, H - 1] = self._ih[0][2]
                hon[r, H - 1] = True
                hoc[r, H - 1] = self._ih[0][1]
        if self.keep_flags:
            self._deg_parts.append(deg)
            self._fo_parts.append(fo)
        return {"hp": hp, "ho": ho, "ha": ha, "hon": hon, "hoc": hoc}

    @property
    def fstats(self) -> Dict[str, int]:
        return {"link_retries": int(self._link_retries),
                "failovers": int(self._failovers),
                "degraded_accesses": int(self._degraded)}

    def faulted(self) -> Dict:
        """Host-side port/ECMP totals for metrics reconstruction."""
        return {
            "port_keys": self.port_keys,
            "packets": self._pkts.copy(),
            "bytes": self._pkts * self.size,  # goodput: retries move 0 bytes
            "occupied": self._occt.copy(),    # retries DO occupy the wire
            "ecmp": {k: list(v) for k, v in self._ecmp.items()},
        }

    def flags(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-access ``(degraded, failover)`` availability flags over
        every ordinal range built so far (empty without ``keep_flags``)."""
        if not self._deg_parts:
            z = np.zeros(0, bool)
            return z, z
        return (np.concatenate(self._deg_parts),
                np.concatenate(self._fo_parts))

    # ------------------------------------------------- checkpoint support
    def state(self) -> Dict:
        """The accumulator totals as a flat-array pytree (checkpointable)."""
        deg, fo = self.flags()
        return {"pkts": self._pkts.copy(), "occt": self._occt.copy(),
                "ecmp": {k: np.asarray(v, np.int64)
                         for k, v in self._ecmp.items()},
                "counters": np.asarray(
                    [self._link_retries, self._failovers, self._degraded],
                    np.int64),
                "deg": deg, "fo": fo}

    def load_state(self, st: Dict) -> None:
        self._pkts = np.asarray(st["pkts"], np.int64).copy()
        self._occt = np.asarray(st["occt"], np.int64).copy()
        self._ecmp = {k: [int(x) for x in np.asarray(v)]
                      for k, v in st["ecmp"].items()}
        c = np.asarray(st["counters"], np.int64)
        self._link_retries = int(c[0])
        self._failovers = int(c[1])
        self._degraded = int(c[2])
        deg = np.asarray(st["deg"], bool)
        fo = np.asarray(st["fo"], bool)
        self._deg_parts = [deg.copy()] if deg.size else []
        self._fo_parts = [fo.copy()] if fo.size else []


def _fault_transport_cols(device, plan, addrs: np.ndarray, size: int):
    """One-shot wrapper over :class:`_FaultColumnBuilder` for whole-trace
    callers.  Returns ``(cols, faulted, fstats, num_ports, num_hops,
    degraded_flags, failover_flags)``."""
    addrs = np.asarray(addrs, np.int64)
    b = _FaultColumnBuilder(device, plan, size, int(addrs.size))
    d = b.columns(addrs, 0)
    deg, fo = b.flags()
    return ((d["hp"], d["ho"], d["ha"], d["hon"], d["hoc"]), b.faulted(),
            b.fstats, b.num_ports, b.num_hops, deg, fo)


# ------------------------------------------------------------------ runner
def _init_carry(cfg: StackConfig, state, start_tick, mspec=None,
                want_lat: bool = True):
    """The full replay carry pytree at ``start_tick`` — LFB slots, issue
    clock, stamp counter, port busy-untils, the stacked media/flash state,
    and the aux (metrics / streaming-summary / QoS) accumulators.  Built
    eagerly by the chunked driver (so it can be buffer-donated across
    chunk calls) and traced by the one-shot entry points; both produce the
    identical structure, which is what makes chunked replay tick-identical
    to one-shot at any chunk size."""
    ecmp = cfg.num_routes > 1
    vec_pb = ecmp or cfg.fault_hops
    aux0 = {}
    if mspec is not None:
        from repro.core.replay import metrics as _metrics
        if not want_lat:
            aux0["acc"] = jnp.zeros((_metrics.acc_rows(mspec, 1, 1), 4),
                                    jnp.int64)
            aux0["med"] = jnp.zeros(len(_metrics.MEDIA_COUNTERS[cfg.kind]),
                                    jnp.int64)
        aux0["q"] = (jnp.zeros(cfg.num_ports, jnp.int64) if vec_pb
                     else tuple(_i64(0) for _ in range(cfg.num_ports)))
        if cfg.qos_ports:
            aux0["qthr"] = (jnp.zeros(cfg.num_ports, jnp.int64) if vec_pb
                            else tuple(_i64(0) for _ in range(cfg.num_ports)))
            if cfg.fault_hops:
                # retries decouple the QoS virtual clock from the port
                # busy-until, so the fault lane carries it explicitly
                aux0["vft"] = jnp.zeros(cfg.num_ports, jnp.int64)
    if not want_lat:
        aux0["first"] = _i64(BIG)
        aux0["last"] = _i64(start_tick)
        aux0["sum"] = _i64(0)
    return (jnp.full(cfg.outstanding, start_tick, jnp.int64),  # LFB slots
            _i64(start_tick),                                  # issue clock
            _i64(1),                                           # stamp counter
            # port busy-until: positional tuple on a fixed route (fuses into
            # elementwise work), an indexable vector under ECMP/fault hops
            jnp.zeros(cfg.num_ports, jnp.int64) if vec_pb
            else tuple(_i64(0) for _ in range(cfg.num_ports)),
            state,
            aux0)


def _scan_chunk(cfg: StackConfig, p: Dict, carry, xs: Dict, block=1,
                mspec=None, want_lat=True, size=64):
    """Scan one contiguous span of accesses from an explicit carry.

    ``xs`` is a dict of per-access columns: ``addr``/``wr`` always,
    ``route`` under ECMP, the five ``hp``/``ho``/``ha``/``hon``/``hoc``
    hop columns under fault hops, and optionally ``valid`` — the ragged-
    tail mask.  A masked step computes normally but commits *nothing*:
    one blanket ``where`` keeps the entire previous carry (busy-untils,
    media/GC state, stamp counter, every accumulator), so a zero-padded
    tail chunk is a pure no-op and any chunking of the trace replays
    tick-identically to one shot.  Key presence is static, so the
    unmasked (full-chunk) program compiles without the gate."""
    fh = cfg.fault_hops
    ecmp = cfg.num_routes > 1
    masked = "valid" in xs

    def step(carry, x):
        slots, now, ctr, pb, st, aux = carry
        addr, wr = x["addr"], x["wr"]
        with jax.named_scope("lfb"):
            k = jnp.argmin(slots)
            issue = jnp.maximum(now, slots[k])
        posted = wr if cfg.posted_writes else jnp.zeros((), bool)
        qacc = aux.get("q")
        qthr = aux.get("qthr")
        vft = aux.get("vft")
        if fh:
            pb, t, qacc, vft, qthr = _transport_cols(
                cfg, p, pb, issue, (x["hp"], x["ho"], x["ha"], x["hon"],
                                    x["hoc"]), qacc, vft, qthr)
        elif ecmp:
            pb, t, qacc, qthr = _transport_ecmp(cfg, p, pb, issue,
                                                x["route"], qacc, qthr)
        else:
            pb, t, qacc, qthr = _transport(cfg, p, pb, issue, qacc, qthr)
        st, out = stack.step(cfg, p, st, dict(
            lane=0, flash_lane=0, t=t, addr=addr, write=wr, posted=posted,
            ctr=ctr))
        done = out["done"]
        with jax.named_scope("telemetry"):
            from repro.core.replay import metrics as _metrics
            if mspec is not None:
                aux = {**aux, "q": qacc}
                if qthr is not None:
                    aux["qthr"] = qthr
                if vft is not None:
                    aux["vft"] = vft
                if "acc" in aux:
                    aux["med"] = aux["med"] + _metrics.media_increments(
                        cfg.kind, wr, out)
                    aux["acc"] = _metrics.acc_update(
                        mspec, aux["acc"], host=0, dev=0, n_hosts=1,
                        n_devs=1, issue=issue, done=done, size=size,
                        hit=out["hit"])
            if not want_lat:
                aux = {**aux,
                       "first": jnp.minimum(aux["first"], issue),
                       "last": jnp.maximum(aux["last"], done),
                       "sum": aux["sum"] + (done - issue)}
            flags = _metrics.event_flags(cfg.kind, out,
                                         mspec is not None and want_lat)
        with jax.named_scope("lfb"):
            new = (slots.at[k].set(done), issue + p["issue_ov"], ctr + 1,
                   pb, st, aux)
        if masked:
            v = x["valid"]
            new = jax.tree.map(lambda old, nxt: jnp.where(v, nxt, old),
                               carry, new)
        ys = (issue, done, flags) if want_lat else None
        return new, ys

    return jax.lax.scan(step, carry, xs, unroll=block)


def _scan_stack(cfg: StackConfig, p: Dict, state, addrs, writes, start_tick,
                routes=None, cols=None, block=1, mspec=None, want_lat=True,
                size=64):
    """The scan proper, parameterized by the initial stacked state so sweeps
    can vary it per vmap lane (e.g. capacity via disabled frames).
    ``state`` is a :func:`repro.core.replay.stack.init_state` pytree with
    one media lane.  ``routes`` is the per-access ECMP choice column
    (required when ``cfg.num_routes > 1``, ignored otherwise).  ``block``
    is the blocked replay width: the scan body replays ``block`` accesses
    per sequential step (scan unroll), with the carry crossing block seams
    untouched — tick-identical at any block size, but the per-step dispatch
    floor is paid once per block instead of once per access.

    ``mspec`` (a :class:`~repro.core.replay.metrics.MetricsSpec`, static)
    grows the carry with the telemetry accumulators.  With per-access
    outputs (``want_lat=True``) that is *only* the per-port queueing (and,
    on weighted-QoS mounts, throttle-counter) scalars: every media counter
    is packed as an event bit into the flags column
    (:data:`metrics.FLAG_EVENT_BITS`) and the histogram/window/counter
    fold is deferred to first bundle access, so the metrics lane stays
    within a few percent of the bare scan.  In streaming mode the
    histogram+window scatter and the media counter-vector add ride the
    carry instead — O(buckets+windows) state, no per-access outputs to
    fold.  ``want_lat=False`` drops the per-access
    stacked outputs entirely (``ys=None``), carrying only first-issue /
    last-done / latency-sum scalars — O(buckets+windows) output for a
    trace of any length.  Both knobs default off, leaving the compiled
    no-metrics program byte-identical to the legacy body (the aux carry is
    an empty pytree)."""
    ecmp = cfg.num_routes > 1
    fh = cfg.fault_hops
    if ecmp and routes is None:
        # callers without a route column (e.g. cache_design_sweep) follow
        # the replay layer's fallback contract, so refuse accordingly
        raise ReplayUnsupported(
            "ECMP stack needs a per-access route column; this entry point "
            "supports single-route mounts only (use engine='python')")
    if fh and cols is None:
        raise ReplayUnsupported(
            "fault-hops stack needs precomputed per-access hop columns; "
            "use ReplayEngine.run_arrays (or engine='python')")
    xs = {"addr": addrs, "wr": writes}
    if fh:
        xs.update(zip(("hp", "ho", "ha", "hon", "hoc"), cols))
    elif ecmp:
        xs["route"] = routes
    init = _init_carry(cfg, state, start_tick, mspec, want_lat)
    carry, ys = _scan_chunk(cfg, p, init, xs, block=block, mspec=mspec,
                            want_lat=want_lat, size=size)
    issues, dones, flags = ys if want_lat else (None, None, None)
    return issues, dones, flags, carry[4], carry[5]


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7, 8))
def _run_stack(cfg: StackConfig, p: Dict, addrs, writes, start_tick,
               block: int = 1, mspec=None, want_lat: bool = True,
               size: int = 64):
    return _scan_stack(cfg, p, stack.init_state(cfg), addrs, writes,
                       start_tick, block=block, mspec=mspec,
                       want_lat=want_lat, size=size)


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9))
def _run_stack_ecmp(cfg: StackConfig, p: Dict, addrs, writes, routes,
                    start_tick, block: int = 1, mspec=None,
                    want_lat: bool = True, size: int = 64):
    return _scan_stack(cfg, p, stack.init_state(cfg), addrs, writes,
                       start_tick, routes=routes, block=block, mspec=mspec,
                       want_lat=want_lat, size=size)


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9))
def _run_stack_faulted(cfg: StackConfig, p: Dict, addrs, writes, cols,
                       start_tick, block: int = 1, mspec=None,
                       want_lat: bool = True, size: int = 64):
    return _scan_stack(cfg, p, stack.init_state(cfg), addrs, writes,
                       start_tick, cols=cols, block=block, mspec=mspec,
                       want_lat=want_lat, size=size)


# --------------------------------------------------------------- streaming
@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6, 7),
                   donate_argnums=(2,))
def _replay_chunk(cfg: StackConfig, p: Dict, carry, xs: Dict, block: int = 1,
                  mspec=None, want_lat: bool = True, size: int = 64):
    """One jitted chunk of the streaming replay.  The carry is donated:
    XLA reuses its buffers for the output carry, so threading state across
    an arbitrarily long trace allocates O(chunk), not O(trace)."""
    return _scan_chunk(cfg, p, carry, xs, block=block, mspec=mspec,
                       want_lat=want_lat, size=size)


def _pad_rows(v: np.ndarray, chunk: int) -> np.ndarray:
    v = np.asarray(v)
    pad = chunk - v.shape[0]
    if pad <= 0:
        return v
    return np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])


def _dealias(tree):
    """Copy any carry leaf whose device buffer aliases an earlier leaf.

    XLA may return two identical outputs (e.g. a never-touched port's
    busy-until and its zero QoS counter) in ONE shared buffer; donating
    that carry back would donate the same buffer twice, which XLA
    rejects.  Copies only the duplicated (scalar-sized) leaves."""
    seen = set()

    def fix(x):
        try:
            ptr = x.unsafe_buffer_pointer()
        except Exception:
            return x
        if ptr in seen:
            return jnp.array(x, copy=True)
        seen.add(ptr)
        return x

    return jax.tree.map(fix, tree)


def _restore_carry(template, flat: Dict[str, np.ndarray]):
    """Rebuild a carry pytree from the flat ``{path: ndarray}`` form a
    checkpoint snapshot stores, validated leaf by leaf against the
    structure/shape/dtype of a freshly built ``template`` (so a snapshot
    from a different config or chunk program fails loudly, never
    silently).  Must run under ``enable_x64``."""
    from repro.checkpoint.manager import _flatten

    flat_t, treedef = _flatten(template)
    leaves = []
    for key, tmpl in flat_t.items():
        arr = flat.get(key)
        if arr is None:
            raise KeyError(f"resume state missing carry leaf {key!r}")
        tmpl = jnp.asarray(tmpl)
        arr = np.asarray(arr)
        if tuple(arr.shape) != tuple(tmpl.shape):
            raise ValueError(
                f"resume carry leaf {key!r} has shape {arr.shape}, "
                f"expected {tuple(tmpl.shape)} — snapshot from a "
                "different replay configuration?")
        leaves.append(jnp.asarray(arr, dtype=tmpl.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _chunked_scan(cfg: StackConfig, p: Dict, chunks, n: int, chunk: int,
                  start_tick, block=1, mspec=None, want_lat=True, size=64,
                  carry=None, seen=0, parts=None, on_chunk=None):
    """Outer streaming loop: replay ``n`` accesses arriving as an iterator
    of ``(lo, hi, cols)`` numpy chunk dicts, threading the full carry
    pytree across chunk boundaries with buffer donation.  A short chunk is
    zero-padded up to ``chunk`` and masked with a per-access ``valid``
    column (masked steps advance *nothing* — see :func:`_scan_chunk`), so
    the jitted chunk program compiles at most twice (full chunk + masked
    chunk) and the result is tick-identical to the one-shot scan at any
    chunk size.  Must run under ``enable_x64``; ``chunks`` must cover
    exactly ``[seen, n)`` in order.

    ``carry``/``seen``/``parts`` resume a previously checkpointed run from
    access ``seen`` (default: a fresh carry from access 0).  ``on_chunk``,
    if given, fires as ``on_chunk(seen, carry, parts)`` after each chunk
    lands — the carry is live (not yet donated to the next chunk), so a
    checkpoint hook can ``device_get`` it safely."""
    if carry is None:
        carry = _init_carry(cfg, stack.init_state(cfg), _i64(start_tick),
                            mspec, want_lat)
    parts = list(parts) if parts else []
    seen = int(seen)
    for lo, hi, cols in chunks:
        m = hi - lo
        if not 0 < m <= chunk or lo != seen:
            raise AssertionError(
                f"chunk iterator out of order: [{lo}, {hi}) after {seen}")
        seen = hi
        if m < chunk:
            cols = {k: _pad_rows(v, chunk) for k, v in cols.items()}
            cols["valid"] = np.arange(chunk) < m
        xs = {k: jnp.asarray(v) for k, v in cols.items()}
        carry, ys = scopes.run(_replay_chunk, cfg, p, _dealias(carry), xs,
                               block, mspec, want_lat, size)
        if want_lat:
            iss, dn, fl = ys
            parts.append((np.asarray(iss[:m]), np.asarray(dn[:m]),
                          np.asarray(fl[:m])))
        if on_chunk is not None:
            on_chunk(seen, carry, parts)
    if seen != n:
        raise AssertionError(f"chunk iterator produced {seen} of {n} accesses")
    if want_lat:
        issues = np.concatenate([x[0] for x in parts])
        dones = np.concatenate([x[1] for x in parts])
        flags = np.concatenate([x[2] for x in parts])
    else:
        issues = dones = flags = None
    return issues, dones, flags, carry[4], carry[5]


# ------------------------------------------------------------------ facade
@dataclass
class ReplayResult(TraceResult):
    """A :class:`TraceResult` plus the per-access tensors the fused scan
    already produced for free."""

    latency_ticks: Optional[np.ndarray] = None   # done - issue, per access
    hit_flags: Optional[np.ndarray] = None
    evict_flags: Optional[np.ndarray] = None
    gc_runs: int = 0                             # flash GC collections run
    # per-access poison status (bit 6 of the flags word) when an active
    # fault plan schedules poison; None otherwise.  Status only — a
    # poisoned read never fabricates latency.
    poison_flags: Optional[np.ndarray] = None
    # tick-windowed availability series + degraded-mode summary
    # (metrics.availability_series) when a transport fault plan is active
    # and per-access outputs were kept.  Host-side observability only —
    # deliberately outside the python-parity MetricsBundle schema.
    availability: Optional[Dict] = None

    @property
    def hits(self) -> int:
        return int(self.hit_flags.sum()) if self.hit_flags is not None else 0


class ReplayEngine:
    """Fused, vectorized stand-in for :class:`TraceDriver` (one host).

    ``run`` is tick-identical to ``TraceDriver(device, ...).run`` for the
    supported stacks (all five paper devices, directly attached or mounted
    behind a switch fabric; cache policies lru/fifo/direct; FTL greedy GC
    included).  Unsupported shapes raise :class:`ReplayUnsupported` so
    callers can fall back.

    ``chunk_size`` (on ``run``/``run_arrays``) switches to the streaming
    chunk loop — same ticks, same metrics, O(chunk) peak *device* input
    residency; ``run_store`` additionally streams the input columns from
    an on-disk :class:`~repro.data.trace_store.TraceStore`, so the host
    never materializes the trace either.
    """

    def __init__(self, device, outstanding: int = 32,
                 issue_overhead_ns: float = 0.5,
                 posted_writes: bool = True, block_size: int = 1,
                 metrics=None) -> None:
        self.device = device
        self.outstanding = max(1, outstanding)
        self.issue_overhead_ns = issue_overhead_ns
        self.posted_writes = posted_writes
        self.block_size = validate_block_size(block_size)
        self.metrics = metrics        # Optional[MetricsSpec]

    def run(self, trace, start_tick: int = 0,
            return_latencies: bool = True,
            chunk_size: Optional[int] = None) -> ReplayResult:
        addrs, writes, size = trace_to_arrays(trace)
        return self.run_arrays(addrs, writes, size=size,
                               start_tick=start_tick,
                               return_latencies=return_latencies,
                               chunk_size=chunk_size)

    # shared refusal + fault-plan discovery for every entry point
    def _common_refusals(self, n: int, start_tick: int):
        if n == 0:
            raise ReplayUnsupported("empty trace")
        if n > MAX_ACCESSES:
            raise ReplayUnsupported(
                f"trace longer than {MAX_ACCESSES} accesses (packed-stamp "
                "budget); split the trace or use engine='python'")
        if start_tick < 0 and getattr(getattr(self.device, "fabric", None),
                                      "qos_enabled", False):
            # with start_tick >= 0 a lone origin's QoS floor provably never
            # binds (see spec._fabric_hops); negative ticks void the proof
            raise ReplayUnsupported(
                "QoS replay needs start_tick >= 0; use engine='python'")

    def _active_plan(self):
        # active fault plan discovery: install() sets it on the mount (and
        # on the shared fabric); direct devices carry it themselves
        plan = getattr(self.device, "fault_plan", None)
        if plan is None:
            plan = getattr(getattr(self.device, "fabric", None),
                           "fault_plan", None)
        if plan is not None and not plan.active:
            plan = None
        return plan

    def run_arrays(self, addrs: np.ndarray, writes: np.ndarray, *,
                   size: int = 64, start_tick: int = 0,
                   return_latencies: bool = True,
                   chunk_size: Optional[int] = None) -> ReplayResult:
        addrs = np.asarray(addrs, np.int64)
        writes = np.asarray(writes, bool)
        self._common_refusals(int(addrs.size), start_tick)
        if chunk_size is not None and int(chunk_size) < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size!r}")
        mspec = self.metrics
        want_lat = bool(return_latencies)
        plan = self._active_plan()
        with TraceAnnotation("replay.build"):
            cfg, params = build_stack(
                self.device, size=size, outstanding=self.outstanding,
                issue_overhead_ns=self.issue_overhead_ns,
                posted_writes=self.posted_writes, n_accesses=addrs.size,
                max_addr=int(addrs.max(initial=0)),
                counters=mspec is not None)
        routes = None
        fcols = None
        faulted = None
        deg_flags = fo_flags = None
        fstats = {"link_retries": 0, "failovers": 0, "degraded_accesses": 0}
        if (plan is not None and (plan.has_link or plan.has_down)
                and isinstance(self.device, FabricAttachedDevice)):
            # transport faults: replace the static route tensors with
            # per-access hop columns (raises DeviceUnreachable exactly
            # where the interpreted driver would)
            (fcols, faulted, fstats, n_ports, n_hops, deg_flags,
             fo_flags) = _fault_transport_cols(self.device, plan, addrs,
                                               size)
            qp = tuple(
                i for i, key in enumerate(faulted["port_keys"])
                if self.device.fabric.ports[key].qos_enabled)
            cfg = dataclasses.replace(cfg, fault_hops=True,
                                      num_hops=n_hops, num_ports=n_ports,
                                      num_routes=1, qos_ports=qp)
            params = {k: v for k, v in params.items()
                      if k not in ("hop_port", "hop_occ", "hop_after")}
        poisoned = None
        if plan is not None and plan.has_poison:
            poisoned = plan.poisoned_np(
                0, np.arange(addrs.size, dtype=np.int64), writes)
        # the device sees dense page ids; ``addrs`` stays real for routes
        # and the metrics fold
        cfg, dev_addrs = compact_pages(cfg, addrs)
        with enable_x64(True):
            with TraceAnnotation("replay.put"):
                pj = jax.tree.map(jnp.asarray, params)
            if cfg.num_routes > 1:
                from repro.core.replay.spec import access_route_choices
                routes = access_route_choices(self.device, addrs)
            if chunk_size is not None:
                chunk = int(chunk_size)
                n = int(addrs.size)

                def _feed():
                    for lo in range(0, n, chunk):
                        hi = min(lo + chunk, n)
                        d = {"addr": dev_addrs[lo:hi], "wr": writes[lo:hi]}
                        if cfg.fault_hops:
                            for key, c in zip(("hp", "ho", "ha", "hon",
                                               "hoc"), fcols):
                                d[key] = c[lo:hi]
                        elif cfg.num_routes > 1:
                            d["route"] = routes[lo:hi]
                        yield lo, hi, d

                with TraceAnnotation("replay.run"):
                    issues, dones, flags, final, aux = _chunked_scan(
                        cfg, pj, _feed(), n, chunk, start_tick,
                        self.block_size, mspec, want_lat, size)
            else:
                with TraceAnnotation("replay.put"):
                    xs = [jnp.asarray(dev_addrs), jnp.asarray(writes)]
                    if cfg.fault_hops:
                        runner = _run_stack_faulted
                        xs.append(tuple(jnp.asarray(c) for c in fcols))
                    elif cfg.num_routes > 1:
                        runner = _run_stack_ecmp
                        xs.append(jnp.asarray(routes))
                    else:
                        runner = _run_stack
                    xs.append(_i64(start_tick))
                with TraceAnnotation("replay.run"):
                    issues, dones, flags, final, aux = scopes.run(
                        runner, cfg, pj, *xs, self.block_size, mspec,
                        want_lat, size)
            if want_lat:
                with TraceAnnotation("replay.fetch"):
                    issues, dones, flags = (np.asarray(issues),
                                            np.asarray(dones),
                                            np.asarray(flags))
            return self._finish(
                cfg, n=int(addrs.size), size=size, start_tick=start_tick,
                want_lat=want_lat, issues=issues, dones=dones, flags=flags,
                final=final, aux=aux, plan=plan, fstats=fstats,
                poisoned=poisoned, faulted=faulted, writes=writes,
                addrs=addrs, routes=routes, deg_flags=deg_flags,
                fo_flags=fo_flags)

    def run_store(self, store, *, chunk_size: int, start_tick: int = 0,
                  return_latencies: bool = True, chunk_iter=None,
                  resume_state: Optional[Dict] = None,
                  on_chunk=None) -> ReplayResult:
        """Streaming replay from an on-disk columnar trace
        (:class:`~repro.data.trace_store.TraceStore`, or anything
        duck-typed like one: ``n``, ``size``, ``max_addr``, ``writes()``
        and ``chunks(chunk_size, start=...)``).  Input residency is
        O(chunk) — columns are memmap-sliced per chunk (optionally through
        a prefetching ``chunk_iter``; see
        :func:`repro.core.replay.stream.replay_stream`), the jitted chunk
        program donates its carry, and nothing host-side ever holds the
        full addr column.  With ``return_latencies=True`` the per-access
        *outputs* are still materialized (inherently O(trace)); pass
        ``return_latencies=False`` for bounded-memory replay end to end.

        Every active fault class streams, transport included: link-retry /
        down-window plans get their per-access hop columns built chunk by
        chunk (:class:`_FaultColumnBuilder` — static shapes derive from
        the plan's down segments, never from the trace), tick-identical to
        the one-shot fault lane.

        ``on_chunk(seen, snapshot)`` fires after each chunk lands;
        ``snapshot()`` captures the full resumable state (carry pytree,
        per-access output parts, feed accumulators) as host numpy — the
        checkpoint layer decides cadence and persistence.  Passing a
        previously captured snapshot back as ``resume_state`` (with
        ``chunk_iter`` starting at ``resume_state['seen']``, or ``None``
        to let the store seek) continues the run bit-exactly."""
        n = int(store.n)
        size = int(store.size)
        chunk = int(chunk_size)
        if chunk < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size!r}")
        self._common_refusals(n, start_tick)
        mspec = self.metrics
        want_lat = bool(return_latencies)
        plan = self._active_plan()
        builder = None
        if (plan is not None and (plan.has_link or plan.has_down)
                and isinstance(self.device, FabricAttachedDevice)):
            builder = _FaultColumnBuilder(self.device, plan, size, n,
                                          keep_flags=want_lat)
        with TraceAnnotation("replay.build"):
            cfg, params = build_stack(
                self.device, size=size, outstanding=self.outstanding,
                issue_overhead_ns=self.issue_overhead_ns,
                posted_writes=self.posted_writes, n_accesses=n,
                max_addr=int(store.max_addr), counters=mspec is not None)
        if builder is not None:
            qp = tuple(
                i for i, key in enumerate(builder.port_keys)
                if self.device.fabric.ports[key].qos_enabled)
            cfg = dataclasses.replace(cfg, fault_hops=True,
                                      num_hops=builder.num_hops,
                                      num_ports=builder.num_ports,
                                      num_routes=1, qos_ports=qp)
            params = {k: v for k, v in params.items()
                      if k not in ("hop_port", "hop_occ", "hop_after")}
        ecmp = cfg.num_routes > 1
        K = 0
        route_counts = None
        if ecmp:
            K = len(self.device.fabric.paths(self.device.host,
                                             self.device.device_node))
            route_counts = np.zeros(K, np.int64)
        has_poison = plan is not None and plan.has_poison
        psum = 0
        poison_parts: List[np.ndarray] = []
        seen0 = 0
        parts0 = None
        if resume_state is not None:
            seen0 = int(resume_state["seen"])
            if not 0 <= seen0 <= n:
                raise ValueError(
                    f"resume cursor {seen0} outside trace of {n} accesses")
            parts0 = ([tuple(np.asarray(a) for a in t)
                       for t in resume_state["parts"]] if want_lat else None)
            psum = int(resume_state.get("psum", 0))
            poison_parts = [np.asarray(x, bool)
                            for x in resume_state.get("poison_parts", [])]
            if route_counts is not None and \
                    resume_state.get("route_counts") is not None:
                route_counts[:] = np.asarray(resume_state["route_counts"])
            if builder is not None and \
                    resume_state.get("builder") is not None:
                builder.load_state(resume_state["builder"])
        if chunk_iter is not None:
            src = chunk_iter
        elif seen0:
            src = store.chunks(chunk, start=seen0)
        else:
            src = store.chunks(chunk)   # duck-typed stores may lack start=

        def _feed():
            nonlocal psum
            from repro.core.fabric.fabric import LINE_BYTES
            from repro.core.fabric.routing import flow_choices
            for lo, hi, cols in src:
                d = {"addr": np.asarray(cols["addr"], np.int64),
                     "wr": np.asarray(cols["wr"], bool)}
                if builder is not None:
                    d.update(builder.columns(d["addr"], lo))
                elif ecmp:
                    r = flow_choices(self.device.host,
                                     self.device.device_node,
                                     d["addr"] // LINE_BYTES, K)
                    route_counts[:] += np.bincount(r, minlength=K)
                    d["route"] = np.asarray(r, np.int32)
                if has_poison:
                    pz = plan.poisoned_np(
                        0, np.arange(lo, hi, dtype=np.int64), d["wr"])
                    psum += int(pz.sum())
                    if want_lat:
                        poison_parts.append(np.asarray(pz, bool))
                yield lo, hi, d

        def _snapshot(seen, carry, parts):
            # everything the run needs to continue from `seen`, as host
            # numpy — feed accumulators are exactly in sync because the
            # feed builds columns lazily, one pulled chunk at a time
            from repro.checkpoint.manager import _flatten
            return {
                "seen": int(seen),
                "carry": {k: np.asarray(jax.device_get(v))
                          for k, v in _flatten(carry)[0].items()},
                "parts": [tuple(np.asarray(a) for a in t) for t in parts],
                "psum": int(psum),
                "route_counts": (None if route_counts is None
                                 else route_counts.copy()),
                "poison_parts": [np.asarray(x, bool) for x in poison_parts],
                "builder": builder.state() if builder is not None else None,
            }

        cb = None
        if on_chunk is not None:
            def cb(seen, carry, parts):
                on_chunk(seen, lambda: _snapshot(seen, carry, parts))

        with enable_x64(True):
            with TraceAnnotation("replay.put"):
                pj = jax.tree.map(jnp.asarray, params)
                carry0 = None
                if resume_state is not None:
                    template = _init_carry(cfg, stack.init_state(cfg),
                                           _i64(start_tick), mspec, want_lat)
                    carry0 = _restore_carry(template, resume_state["carry"])
            with TraceAnnotation("replay.run"):
                issues, dones, flags, final, aux = _chunked_scan(
                    cfg, pj, _feed(), n, chunk, start_tick, self.block_size,
                    mspec, want_lat, size, carry=carry0, seen=seen0,
                    parts=parts0, on_chunk=cb)
            poisoned = None
            if has_poison:
                poisoned = (np.concatenate(poison_parts) if want_lat
                            else None)
            deg_flags = fo_flags = None
            if builder is not None:
                fstats = dict(builder.fstats)
                fstats["poisoned_reads"] = psum
                faulted = builder.faulted()
                if want_lat:
                    deg_flags, fo_flags = builder.flags()
            else:
                fstats = {"link_retries": 0, "failovers": 0,
                          "degraded_accesses": 0, "poisoned_reads": psum}
                faulted = None
            return self._finish(
                cfg, n=n, size=size, start_tick=start_tick,
                want_lat=want_lat, issues=issues, dones=dones, flags=flags,
                final=final, aux=aux, plan=plan, fstats=fstats,
                poisoned=poisoned, faulted=faulted,
                writes=(store.writes() if (mspec is not None and want_lat)
                        else None),
                addrs=None, routes=None, n_accesses=n,
                route_counts=route_counts, poison_total=psum,
                deg_flags=deg_flags, fo_flags=fo_flags)

    # shared post-processing: health check, poison bit, fault counters,
    # metrics bundle, result assembly (identical for one-shot / chunked /
    # store-streamed paths — called under enable_x64)
    @functools.partial(annotate_function, name="replay.finish")
    def _finish(self, cfg, *, n, size, start_tick, want_lat, issues, dones,
                flags, final, aux, plan, fstats, poisoned, faulted, writes,
                addrs, routes, n_accesses=None, route_counts=None,
                poison_total=None, deg_flags=None, fo_flags=None):
        bad, gcs = stack.flash_health(final)
        bad, gcs = bool(bad), int(gcs)
        if want_lat:
            issues = np.asarray(issues)
            dones = np.asarray(dones)
            flags = np.asarray(flags)
            if poisoned is not None:
                # status bit only (bit 6): the hist/media folds read
                # bits 0..5, so the bundle stays untouched by poison
                flags = flags | (poisoned.astype(np.int32) << 6)
        fdict = None
        if plan is not None:
            rr, rb = stack.fault_counters(final)
            if poison_total is None:
                poison_total = (int(poisoned.sum()) if poisoned is not None
                                else 0)
            fdict = {
                "link_retries": fstats["link_retries"],
                "failovers": fstats["failovers"],
                "degraded_accesses": fstats["degraded_accesses"],
                "nand_read_retries": int(rr),
                "retired_blocks": int(rb),
                "poisoned_reads": poison_total,
            }
        mb = None
        mspec = self.metrics
        if mspec is not None:
            from repro.core.replay import metrics as _metrics
            fcnt = stack.flash_counters(final)
            fcnt = np.asarray(fcnt) if fcnt is not None else None
            qthr = aux.get("qthr")
            if want_lat:
                mb = _metrics.bundle_single_deferred(
                    mspec, self.device, cfg, issues, dones, flags,
                    writes, aux["q"], fcnt, addrs, routes, size,
                    faults=fdict, faulted=faulted, qthr=qthr,
                    n_accesses=n_accesses, route_counts=route_counts)
            else:
                mb = _metrics.bundle_single_fused(
                    mspec, self.device, cfg, aux["acc"], aux["med"],
                    aux["q"], fcnt, addrs, routes, size,
                    faults=fdict, faulted=faulted, qthr=qthr,
                    n_accesses=n_accesses, route_counts=route_counts)
        if bad:
            raise ReplayUnsupported(
                "FTL ran out of free blocks during GC (device overfilled) — "
                "the interpreted path raises there too; shrink the trace or "
                "use engine='python' for the exact error")
        avail = None
        if (want_lat and deg_flags is not None
                and int(np.asarray(deg_flags).size) == n):
            from repro.core.replay import metrics as _metrics
            avail = _metrics.availability_series(
                issues, dones, deg_flags, fo_flags,
                spec=self.metrics, start_tick=start_tick)
        if want_lat:
            first = int(issues[0])
            last = max(int(dones.max(initial=0)), start_tick)
            lat_sum = int((dones - issues).sum())
        else:
            first = int(aux["first"])
            last = max(int(aux["last"]), start_tick)
            lat_sum = int(aux["sum"])
        return ReplayResult(
            accesses=n,
            bytes_moved=n * size,
            elapsed_ticks=last - first,
            sum_latency_ticks=lat_sum,
            end_tick=last,
            latency_ticks=dones - issues if want_lat else None,
            hit_flags=(flags & 1).astype(bool) if want_lat else None,
            evict_flags=(flags & 2).astype(bool) if want_lat else None,
            gc_runs=gcs,
            poison_flags=(((flags >> 6) & 1).astype(bool)
                          if want_lat and poisoned is not None else None),
            availability=avail,
            metrics=mb,
        )
