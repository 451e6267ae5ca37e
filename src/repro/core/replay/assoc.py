"""Log-depth associative replay lane: zero sequential scan steps.

The scan engine (:mod:`repro.core.replay.engine`) pays XLA:CPU's per-step
thunk dispatch once per access.  This lane removes the sequential scan
entirely for the stateless media stacks (DRAM- and PMEM-class): every
busy-until chain in the stack is a **max-plus recurrence**

    ``free_i = max(arr_i, free_{i-1}) + svc_i``

which composes associatively — a chain over N accesses is an
:func:`jax.lax.associative_scan` (log depth), and a chain with *constant*
service time collapses further to one ``cummax`` (see :func:`busy_until`
and the tandem stages inside the solver).  Transport hops, the media
occupancy chain, and the issue-pacing recurrence are all of this shape;
PMEM row-hit state and posted-write tails are pure elementwise data.

The one genuine feedback loop is the LFB ring: ``issue_i = max(now_i,
popped_i)`` where ``popped_i`` is the slot freed by an *earlier completion*.
Two facts make it tractable:

* completions are pushed in issue order and every pushed completion is
  ``>=`` every previously popped value (each completion exceeds its own
  issue tick by the stack's fixed minimum latency), so the popped sequence
  is exactly the **sorted** completion stream, offset by the LFB depth;
* the full system is a monotone set of max-plus constraints whose *least*
  fixed point is precisely what the sequential fold computes.

The solver therefore Kleene-iterates the data-parallel closed form
(pacing scan -> tandem transport/media -> sort -> popped floor) from below
until it reaches a fixed point, then **certifies** the candidate:

* fixed point: one more sweep changes nothing;
* strict suffix property: ``min_{j>=i} done_j > popped_i`` for every i,
  which proves the sorted-pop identity held index by index, hence the
  candidate satisfies the *causal* recurrence, whose solution is unique.

A certified solution is tick-identical to ``TraceDriver`` — not "close",
identical (property-tested).  If the iteration does not converge inside
``max_sweeps`` (latency/window-bound traces, where the LFB feedback chains
through most of the trace), the lane raises :class:`ReplayUnsupported` and
callers fall back to the blocked scan — exactness is never bought with
silence.  Convergence is fast (2-4 sweeps) exactly in the streaming regime
the drivers are sized for: ``outstanding ~ latency/occupancy`` (Little's
law) makes the media occupancy chain, not the LFB ring, the binding
constraint.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from repro.core.replay.engine import ReplayResult
from repro.core.replay.spec import (
    ASSOC_KINDS,
    DRAM,
    ReplayUnsupported,
    StackConfig,
    build_stack,
    trace_to_arrays,
)

def _neg(dtype):
    """"Never" sentinel for inactive elements of a gated chain: far enough
    below any tick that max() ignores it even after every accumulated
    service time is added, far enough above the dtype's minimum that the
    additions cannot wrap (callers already require the *real* tick range to
    stay well inside the dtype)."""
    return -(int(jnp.iinfo(dtype).max) // 4)


# ------------------------------------------------------------- primitives
def _affine_max(left, right):
    """Compose two affine-max transforms ``f -> max(f + A, B)`` (left first).

    This is the associative algebra every busy-until fold lives in: one
    element is ``A = svc_i, B = arr_i + svc_i``; a composed segment tracks
    the service it accumulates (A) and the best restart value (B).
    """
    a1, b1 = left
    a2, b2 = right
    return a1 + a2, jnp.maximum(b1 + a2, b2)


SCAN_ROW = 8192


def assoc_scan(fn, elems, identity, reverse=False):
    """:func:`jax.lax.associative_scan` over 1-D leaves, computed as a
    sequential ``lax.scan`` across ``SCAN_ROW``-element rows with a
    log-depth scan inside each row.

    Same values: ``fn`` is associative and the integer arithmetic exact;
    ``identity``, shaped like ``elems`` with one scalar per leaf, is its
    neutral element and pads the last row.  The TPU compiler takes minutes
    over one trace-long 1-D scan at 2^20 elements, and under a second over
    rows.  ``reverse`` needs a commutative ``fn``."""
    leaves, tree = jax.tree.flatten(elems)
    ids = jax.tree.leaves(identity)
    n = leaves[0].shape[0]
    if n <= SCAN_ROW:
        return jax.lax.associative_scan(fn, elems, reverse=reverse)
    pad = (-n) % SCAN_ROW
    rows = tree.unflatten([
        jnp.concatenate([x, jnp.full(pad, e, x.dtype)]).reshape(-1, SCAN_ROW)
        for x, e in zip(leaves, ids)])
    end = 0 if reverse else -1

    def body(carry, r):
        out = fn(carry, jax.lax.associative_scan(fn, r, reverse=reverse))
        return jax.tree.map(lambda o: o[end], out), out

    init = tree.unflatten([jnp.asarray(e, x.dtype)
                           for x, e in zip(leaves, ids)])
    _, out = jax.lax.scan(body, init, rows, reverse=reverse)
    return jax.tree.map(lambda o: o.reshape(-1)[:n], out)


def busy_until(arrivals, services, active=None, init=None):
    """Associative form of the sequential busy-until fold.

    Sequential semantics (the switch-port / link / media / QoS
    virtual-finish-time rule)::

        free = init
        for i in range(N):
            if active[i]:
                free = max(arrivals[i], free) + services[i]
            out[i] = free

    ``services`` may vary per element (QoS-weighted paces, mixed transfer
    sizes); ``active`` gates elements that bypass the chain (e.g. cache
    hits on a fill path).  Returns the chain value right after each
    element, exactly equal to the fold (property-tested).  Log depth per
    row of :func:`assoc_scan`.
    """
    arrivals = jnp.asarray(arrivals)
    services = jnp.asarray(services)
    neg = _neg(jnp.result_type(arrivals, services))
    if init is None:
        init = neg
    if active is None:
        a = services
        b = arrivals + services
    else:
        a = jnp.where(active, services, 0)
        b = jnp.where(active, arrivals + services, neg)
    cum_a, cum_b = assoc_scan(_affine_max, (a, b), (0, neg))
    return jnp.maximum(init + cum_a, cum_b)


def port_busy_until(arrivals, services, ports, num_ports, init=0):
    """Associative form of P independent busy-until chains selected per
    element — the ECMP route-choice shape, where access *i* occupies port
    ``ports[i]`` out of the path set's port union.

    Sequential semantics::

        free = [init] * num_ports
        for i in range(N):
            free[ports[i]] = max(arrivals[i], free[ports[i]]) + services[i]
            out[i] = free[ports[i]]

    Each element is an affine-max transform on a (P,)-vector state that is
    one-hot in its own port; segments compose elementwise per port, so the
    whole interleaved multi-chain history is one associative scan over
    (N, P) accumulants.  Returns each element's own-port value after its
    update, exactly equal to the fold (property-tested).
    """
    arrivals = jnp.asarray(arrivals)
    services = jnp.asarray(services)
    ports = jnp.asarray(ports)
    neg = _neg(jnp.result_type(arrivals, services))
    onehot = jnp.arange(num_ports)[None, :] == ports[:, None]
    a = jnp.where(onehot, services[:, None], 0)
    b = jnp.where(onehot, (arrivals + services)[:, None], neg)
    cum_a, cum_b = jax.lax.associative_scan(_affine_max, (a, b))
    free = jnp.maximum(init + cum_a, cum_b)                    # (N, P)
    return jnp.take_along_axis(free, ports[:, None], axis=1)[:, 0]


def _local_sort(x, block):
    """Sort an array whose elements sit within ``block // 2`` positions of
    their sorted slot (bounded displacement).

    Completion streams have this shape: the media occupancy chain grows by
    at least ``occ`` per access, so two completions can only be out of
    order if their indices are within (tail spread / occ) of each other —
    a bound the caller computes from the device's timing constants.  Two
    passes of small independent sorts (aligned ``block``-wide rows, then
    rows offset by half a block) then produce the full sorted order at
    ~N log(block) cost, vectorized across rows — an order of magnitude
    cheaper than XLA:CPU's whole-array comparator sort at 200k elements.

    The solver certifies the result is globally sorted before trusting it
    (a sorted permutation IS the sort), so an undershot displacement bound
    surfaces as a refusal, never as silent divergence.
    """
    n = x.shape[0]
    big = jnp.iinfo(x.dtype).max
    pad = (-n) % block
    y = jnp.concatenate([x, jnp.full(pad, big, x.dtype)]) if pad else x
    m = y.shape[0]
    y = jnp.sort(y.reshape(-1, block), axis=1).reshape(-1)
    if m > block:
        h = block // 2
        mid = jnp.sort(y[h:m - h].reshape(-1, block), axis=1).reshape(-1)
        y = jnp.concatenate([y[:h], mid, y[m - h:]])
    return y[:n]


# ------------------------------------------------------------------ solver
class _NumpyOps:
    """CPU backend of the solver: numpy's accumulate/sort run the handful
    of vectorized passes in a few ms where XLA:CPU's comparator sort alone
    costs ~70ms at 200k elements."""

    xp = np

    @staticmethod
    def run(stage, cfg, *args, **static):
        return stage(_NumpyOps, cfg, *args, **static)

    @staticmethod
    def cummax(x):
        return np.maximum.accumulate(x)

    @staticmethod
    def rcummin(x):
        return np.minimum.accumulate(x[::-1])[::-1]

    @staticmethod
    def sort(x, sort_block):
        return np.sort(x)


class _JnpOps:
    """Accelerator backend: the same passes as jnp ops, each stage one
    jitted program (op-by-op dispatch would compile every op and shape
    separately on the accelerator), with the sorted completion stream from
    the bounded-displacement block sort."""

    xp = jnp

    @staticmethod
    def run(stage, cfg, *args, **static):
        return _jitted(stage, tuple(static))(_JnpOps, cfg, *args, **static)

    # assoc_scan, not lax.cummax/cummin: the TPU lowers those to a
    # trace-wide reduce_window, which takes minutes to compile on int64
    @staticmethod
    def cummax(x):
        return assoc_scan(jnp.maximum, x, jnp.iinfo(x.dtype).min)

    @staticmethod
    def rcummin(x):
        return assoc_scan(jnp.minimum, x, jnp.iinfo(x.dtype).max,
                          reverse=True)

    @staticmethod
    def sort(x, sort_block):
        return _local_sort(x, sort_block)


@functools.lru_cache(maxsize=None)
def _jitted(stage, static_names):
    return jax.jit(stage, static_argnums=(0, 1),
                   static_argnames=static_names)


# Solver stages, each ``stage(ops, cfg, ...)``: written once against the
# ops shim so the numpy (CPU) and jnp (accelerator) backends share every
# formula.
def _setup(ops, cfg: StackConfig, p: Dict, addrs, writes, start):
    """Elementwise media data (return-path tails + hit flags) and the
    first issue ticks, paced from ``start`` with no LFB floor."""
    xp = ops.xp
    n = addrs.shape[0]
    posted = writes if cfg.posted_writes else xp.zeros(n, bool)
    if cfg.kind == DRAM:
        tail = xp.where(posted, p["pack"], p["load"])
        hit = xp.zeros(n, bool)
    else:                                    # PMEM: row-buffer locality is
        row = addrs // p["row_bytes"]        # pure data, no timing feedback
        hit = xp.concatenate([xp.zeros(1, bool), row[1:] == row[:-1]])
        lat = p["lat"][xp.where(writes, 1, 0), xp.where(hit, 1, 0)]
        tail = xp.where(posted, p["pack"], lat)
    u = _pacing(ops, p, xp.full(n, start, xp.int64),
                xp.arange(n, dtype=xp.int64))
    return tail, hit, u


def _stage(ops, arr, svc, ar):
    # constant-service busy-until chain seeded at 0 (fresh port/media)
    return ops.xp.maximum(ops.cummax(arr - svc * ar), 0) + svc * (ar + 1)


def _forward(ops, cfg: StackConfig, p: Dict, u, tail, ar):
    """Issue ticks -> completion ticks: the tandem of transport-hop and
    media busy-until chains, mirrored stage for stage."""
    t = u
    for h in range(cfg.num_hops):
        t = _stage(ops, t, p["hop_occ"][h], ar) + p["hop_after"][h]
    t = t + p["rt_extra"]
    return _stage(ops, t, p["occ"], ar) + tail


def _pacing(ops, p: Dict, floor, ar):
    return ops.cummax(floor - p["issue_ov"] * ar) + p["issue_ov"] * ar


def _sweep(ops, cfg: StackConfig, p: Dict, u, tail, start, *,
           sort_block: int):
    """One Kleene sweep: the completions of issue ticks ``u``, the popped
    floor they imply, and the re-paced issue ticks."""
    xp = ops.xp
    n = u.shape[0]
    depth = cfg.outstanding
    ar = xp.arange(n, dtype=xp.int64)
    dones = _forward(ops, cfg, p, u, tail, ar)
    floor, sorted_ok = xp.full(n, start, xp.int64), True
    if n > depth:
        srt = ops.sort(dones, sort_block)
        sorted_ok = (srt[1:] >= srt[:-1]).all()
        floor = xp.where(ar < depth, start,
                         srt[xp.clip(ar - depth, 0, n - 1)])
    u2 = _pacing(ops, p, floor, ar)
    return dones, floor, sorted_ok, u2, (u2 == u).all()


def _suffix_ok(ops, cfg: StackConfig, dones, floor):
    return (ops.rcummin(dones) > floor).all()


def _solve_core(ops, cfg: StackConfig, p: Dict, addrs, writes, start_tick,
                max_sweeps: int, sort_block: int):
    """The certified Kleene solve.  Returns ``(issues, dones, hit_flags,
    sweeps, certified)``."""
    start = int(start_tick)
    tail, hit, u = ops.run(_setup, cfg, p, addrs, writes, start)
    dones = floor = u
    sorted_ok = True
    converged = False
    sweeps = 0
    for sweeps in range(1, max_sweeps + 1):
        dones, floor, sorted_ok, u2, same = ops.run(
            _sweep, cfg, p, u, tail, start, sort_block=sort_block)
        if bool(same):
            converged = True
            break
        u = u2
    # On convergence ``dones == forward(u)`` (the sweep evaluated forward
    # on the value it converged to).  Certification: converged => fixed
    # point; the popped stream was genuinely sorted (a sorted permutation
    # of the completions IS their sort, so an undershot displacement bound
    # in the block sort surfaces here); and the strict suffix property
    # proves the sorted-pop identity was valid at every index — together
    # the candidate solves the causal recurrence, whose solution is unique.
    certified = (converged and bool(sorted_ok)
                 and bool(ops.run(_suffix_ok, cfg, dones, floor)))
    return (np.asarray(u), np.asarray(dones), np.asarray(hit), sweeps,
            certified)


# ------------------------------------------------------------------ facade
class AssocReplayEngine:
    """Fully data-parallel stand-in for :class:`TraceDriver` on stateless
    media stacks (``dram``, ``cxl-dram``, ``pmem``, directly attached or
    fabric-mounted on a single route).

    ``run`` either returns ticks **identical** to
    ``TraceDriver(device, ...).run`` or raises :class:`ReplayUnsupported`
    (stateful media, ECMP fan-out, or a latency-bound trace whose LFB
    feedback defeats the ``max_sweeps`` budget) — never a silently
    approximate result.  Fall back to ``engine="scan"`` on refusal.
    """

    def __init__(self, device, outstanding: int = 32,
                 issue_overhead_ns: float = 0.5,
                 posted_writes: bool = True, max_sweeps: int = 24,
                 backend: str = "auto") -> None:
        if backend not in ("auto", "numpy", "jax"):
            raise ValueError(f"backend must be auto|numpy|jax, got "
                             f"{backend!r}")
        self.device = device
        self.outstanding = max(1, outstanding)
        self.issue_overhead_ns = issue_overhead_ns
        self.posted_writes = posted_writes
        self.max_sweeps = max(1, int(max_sweeps))
        self.backend = backend

    def run(self, trace, start_tick: int = 0) -> ReplayResult:
        addrs, writes, size = trace_to_arrays(trace)
        return self.run_arrays(addrs, writes, size=size,
                               start_tick=start_tick)

    def run_arrays(self, addrs: np.ndarray, writes: np.ndarray, *,
                   size: int = 64, start_tick: int = 0) -> ReplayResult:
        addrs = np.asarray(addrs, np.int64)
        writes = np.asarray(writes, bool)
        if addrs.size == 0:
            raise ReplayUnsupported("empty trace")
        if start_tick < 0 and getattr(getattr(self.device, "fabric", None),
                                      "qos_enabled", False):
            # same contract as ReplayEngine: the lone-origin QoS no-floor
            # proof assumes non-negative ticks
            raise ReplayUnsupported(
                "QoS replay needs start_tick >= 0; use engine='python'")
        plan = getattr(self.device, "fault_plan", None)
        if plan is None:
            plan = getattr(getattr(self.device, "fabric", None),
                           "fault_plan", None)
        if plan is not None and plan.active:
            raise ReplayUnsupported(
                f"active fault plan ({', '.join(plan.class_names())}) "
                "perturbs per-access service times with no associative "
                "closed form; the fused scan lane replays every fault "
                "class tick-identically — use engine='scan' (or "
                "engine='python')")
        cfg, params = build_stack(
            self.device, size=size, outstanding=self.outstanding,
            issue_overhead_ns=self.issue_overhead_ns,
            posted_writes=self.posted_writes, n_accesses=addrs.size,
            max_addr=int(addrs.max(initial=0)))
        if cfg.kind not in ASSOC_KINDS:
            raise ReplayUnsupported(
                f"{cfg.kind!r} media keeps per-access state (cache frames / "
                "flash FTL) with no associative closed form; use "
                "engine='scan' (optionally blocked)")
        if cfg.num_routes > 1:
            raise ReplayUnsupported(
                "ECMP stacks occupy a different port set per access; the "
                "associative lane covers single-route mounts — use "
                "engine='scan'")
        min_lat = int(np.sum(params["hop_occ"]) + np.sum(params["hop_after"])
                      + params["rt_extra"] + params["occ"])
        if min_lat < 1:
            # the sorted-pop certification needs completions to strictly
            # exceed their issue ticks
            raise ReplayUnsupported(
                "zero-latency stack cannot be certified; use engine='scan'")
        occ = int(params["occ"])
        if occ < 1:
            raise ReplayUnsupported(
                "zero media occupancy voids the bounded-displacement sort "
                "(completions need not be locally ordered); use "
                "engine='scan'")
        # Completion displacement bound: the media chain grows >= occ per
        # access, so two completions can only swap order within
        # (tail spread / occ) indices — the block width of the local sort.
        if cfg.kind == DRAM:
            tails = [int(params["load"])]
        else:
            tails = [int(t) for t in np.asarray(params["lat"]).ravel()]
        if self.posted_writes:
            tails.append(int(params["pack"]))
        spread = max(tails) - min(tails)
        sort_block = max(32, 2 * (spread // occ + 1))
        backend = self.backend
        if backend == "auto":
            backend = "numpy" if jax.default_backend() == "cpu" else "jax"
        if backend == "numpy":
            issues, dones, hits, sweeps, certified = _solve_core(
                _NumpyOps, cfg, params, addrs, writes, start_tick,
                self.max_sweeps, sort_block)
        else:
            with enable_x64(True):
                pj = jax.tree.map(jnp.asarray, params)
                issues, dones, hits, sweeps, certified = _solve_core(
                    _JnpOps, cfg, pj, jnp.asarray(addrs),
                    jnp.asarray(writes), start_tick, self.max_sweeps,
                    sort_block)
        if not certified:
            raise ReplayUnsupported(
                f"associative solve not certified after "
                f"{sweeps}/{self.max_sweeps} sweeps (latency-bound "
                "trace: the LFB feedback chains through the whole "
                "trace); use engine='scan'")
        self._last_sweeps = int(sweeps)
        first = int(issues[0])
        last = max(int(dones.max(initial=0)), start_tick)
        return ReplayResult(
            accesses=int(addrs.size),
            bytes_moved=int(addrs.size) * size,
            elapsed_ticks=last - first,
            sum_latency_ticks=int((dones - issues).sum()),
            end_tick=last,
            latency_ticks=dones - issues,
            hit_flags=hits,
            evict_flags=np.zeros(addrs.size, bool),
        )
