"""Pallas TPU kernel: set-associative cache replay (the simulator hot-spot).

The paper's DRAM-cache layer decides hit/miss/evict for every 64 B access;
replaying long address traces against that state machine is the dominant
compute of trace-driven evaluation.  This kernel keeps the whole cache
state — tags, timestamps, dirty bits — in VMEM scratch that persists across
a sequential grid, and streams the trace through in ``chunk``-access blocks
held in SMEM, where per-access scalar loads and stores are legal.

Layout: frame ``f = way * num_sets + set`` lives at ``(f // 128, f % 128)``
of a ``(rows, 128)`` int32 tile (``rows`` a multiple of 8, padding frames
belong to no set), so fully-associative (``num_sets=1``) and direct-mapped
(``ways=1``) caches use every lane alike.  Each access is a fixed handful
of whole-tile vector ops and three int32 reductions: hit, the set's
smallest victim key, and the victim's dirty bit.  Victim keys are unique
within a set (invalid frames key ``NEG + f``, valid frames their stamp, and
stamps are distinct access indices), so "smallest key" picks exactly the
frame the oracle's first-index ``argmin`` picks, with no index reduction.

The update rule is bit-identical to :func:`repro.core.cache.trace_sim._run_trace`
(the lax.scan oracle), which in turn matches the pure-Python policy objects.
Cache replay is inherently sequential (every access depends on the state
left by the previous one), so the kernel is latency-bound: its per-access
cost grows with ``ways * num_sets / 1024`` vregs per vector op.

VMEM: five ``(rows, 128)`` int32 tiles (three state, two layout constants);
for the paper's 4,096-frame cache that is 80 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -(2**31) + 1
BIG = 2**31 - 1
LANES = 128
COL_TILE = 1024          # XLA's TPU tiling of a 1-D int32 array


def _layout(num_sets: int, ways: int):
    """Per-frame constants of the ``(rows, 128)`` tile: the frame's set
    (``-1`` for padding) and its key while invalid (``NEG + f``, so the
    lowest invalid way of a set has the smallest key)."""
    frames = num_sets * ways
    rows = -(-frames // LANES)
    rows += (-rows) % 8
    f = np.arange(rows * LANES, dtype=np.int64)
    set_of = np.where(f < frames, f % num_sets, -1).astype(np.int32)
    inv_key = (NEG + f).astype(np.int32)
    return (set_of.reshape(rows, LANES), inv_key.reshape(rows, LANES))


def _replay_kernel(set_ref, inv_ref, pages_ref, writes_ref, *refs,
                   num_sets: int, chunk: int, is_lru: bool, lat):
    """One grid step replays ``chunk`` accesses.  ``lat`` is ``None`` for
    decisions only, else the latency model of :func:`cache_sim_fused`."""
    if lat is None:
        hits_ref, evicts_ref, tags_ref, meta_ref, dirty_ref = refs
    else:
        (hits_ref, evicts_ref, lat_ref, arr_ref, tags_ref, meta_ref,
         dirty_ref, clock_ref, ring_ref) = refs
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        tags_ref[...] = jnp.full(tags_ref.shape, -1, jnp.int32)
        meta_ref[...] = jnp.zeros(meta_ref.shape, jnp.int32)
        dirty_ref[...] = jnp.zeros(dirty_ref.shape, jnp.int32)
        if lat is not None:
            clock_ref[0] = 0                  # fill path busy-until
            clock_ref[1] = 0                  # previous arrival

            def clear(k, carry):
                ring_ref[k] = 0
                return carry

            jax.lax.fori_loop(0, lat["outstanding"], clear, 0)

    base_t = step * chunk

    def body(i, carry):
        page = pages_ref[i]
        wr = writes_ref[i]
        t = base_t + i + 1
        s = jax.lax.rem(page, num_sets)

        tags = tags_ref[...]
        meta = meta_ref[...]
        dirty = dirty_ref[...]
        valid = tags >= 0
        # a page can only be tagged in its own set, so no set mask here
        match = tags == page
        hit = jnp.max(jnp.where(match, 1, 0)) > 0
        key = jnp.where(set_ref[...] == s,
                        jnp.where(valid, meta, inv_ref[...]), BIG)
        victim = key == jnp.min(key)
        victim_dirty = jnp.max(jnp.where(victim & valid, dirty, 0)) > 0
        dirty_evict = jnp.logical_and(jnp.logical_not(hit), victim_dirty)

        miss = jnp.logical_not(hit)
        fill = victim & miss                  # frame a miss (re)fills
        sel = match | fill                    # frame this access touches
        tags_ref[...] = jnp.where(fill, page, tags)
        meta_ref[...] = jnp.where(sel if is_lru else fill, t, meta)
        keep = jnp.where(hit, 1, 0)           # a hit ORs into the dirty bit
        dirty_ref[...] = jnp.where(sel, (dirty * keep) | wr, dirty)

        hits_ref[i] = jnp.where(hit, 1, 0)
        evicts_ref[i] = jnp.where(dirty_evict, 1, 0)
        if lat is not None:
            # closed-loop arrival (LFB ring), then busy-until queueing on
            # the miss fill path
            slot = jax.lax.rem(base_t + i, lat["outstanding"])
            t_arr = jnp.maximum(clock_ref[1] + lat["issue_ns"], ring_ref[slot])
            busy = clock_ref[0]
            start = jnp.maximum(t_arr, busy)
            done = jnp.where(hit, t_arr + lat["hit_ns"],
                             start + lat["miss_ns"]
                             + jnp.where(dirty_evict, lat["wb_ns"], 0))
            clock_ref[0] = jnp.where(hit, busy, start + lat["miss_occ_ns"])
            clock_ref[1] = t_arr
            ring_ref[slot] = done
            lat_ref[i] = done - t_arr
            arr_ref[i] = t_arr
        return carry

    jax.lax.fori_loop(0, chunk, body, 0)


def _replay(pages, writes, *, num_sets: int, ways: int, policy: str,
            chunk: int, interpret: bool, lat=None):
    """Shared ``pallas_call`` of :func:`cache_sim` and :func:`cache_sim_fused`:
    returns the per-access int32 output columns, trimmed to the trace."""
    if policy not in ("lru", "fifo", "direct"):
        raise ValueError(f"kernel supports lru/fifo/direct, got {policy!r}")
    if policy == "direct" and ways != 1:
        raise ValueError("direct-mapped requires ways == 1")
    if chunk % COL_TILE:
        raise ValueError(
            f"chunk must be a multiple of {COL_TILE}, got {chunk}")
    n = pages.shape[0]
    pad = (-n) % chunk
    pages = jnp.pad(pages.astype(jnp.int32), (0, pad))
    writes = jnp.pad(writes.astype(jnp.int32), (0, pad))
    set_of, inv_key = _layout(num_sets, ways)
    tile = pl.BlockSpec(set_of.shape, lambda c: (0, 0))
    col = pl.BlockSpec((chunk,), lambda c: (c,), memory_space=pltpu.SMEM)
    n_out = 2 if lat is None else 4
    scratch = [pltpu.VMEM(set_of.shape, jnp.int32) for _ in range(3)]
    if lat is not None:
        scratch += [pltpu.SMEM((2,), jnp.int32),
                    pltpu.SMEM((lat["outstanding"],), jnp.int32)]
    kern = functools.partial(_replay_kernel, num_sets=num_sets, chunk=chunk,
                             is_lru=(policy == "lru"), lat=lat)
    outs = pl.pallas_call(
        kern,
        grid=(pages.shape[0] // chunk,),
        in_specs=[tile, tile, col, col],
        out_specs=[col] * n_out,
        out_shape=[jax.ShapeDtypeStruct(pages.shape, jnp.int32)] * n_out,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(set_of), jnp.asarray(inv_key), pages, writes)
    return [o[:n] for o in outs]


@functools.partial(jax.jit, static_argnames=("num_sets", "ways", "policy",
                                             "chunk", "interpret"))
def cache_sim(pages: jnp.ndarray, writes: jnp.ndarray, *, num_sets: int,
              ways: int, policy: str = "lru", chunk: int = 1024,
              interpret: bool = True):
    """Replay a trace. pages: (N,) int32; writes: (N,) bool.
    Returns (hits (N,) bool, dirty_evicts (N,) bool)."""
    hits, evicts = _replay(pages, writes, num_sets=num_sets, ways=ways,
                           policy=policy, chunk=chunk, interpret=interpret)
    return hits.astype(bool), evicts.astype(bool)


@functools.partial(jax.jit, static_argnames=("hit_ns", "miss_ns",
                                             "miss_occ_ns", "wb_ns"))
def fill_latency_assoc(hits, evicts, arr_ns, *, hit_ns: int, miss_ns: int,
                       miss_occ_ns: int, wb_ns: int):
    """Recompute the fused kernel's latency stream from its decisions and
    arrivals with the associative busy-until formulation shared with the
    replay engines (:func:`repro.core.replay.assoc.busy_until`).

    The kernel's fill path is a gated max-plus chain — misses occupy the
    cache-DRAM fill stage for ``miss_occ_ns`` each, hits bypass it — so
    given the arrival stream the whole latency recurrence is one
    associative scan, **bit-identical** to the sequential in-kernel chain
    (tested against both the kernel and the ref twin).  Used by
    ``run_pallas(validate=True)`` to cross-check every kernel run in the
    golden-trace suite.
    """
    from repro.core.replay.assoc import busy_until

    hits = jnp.asarray(hits, bool)
    evicts = jnp.asarray(evicts, bool)
    arr = jnp.asarray(arr_ns)
    miss = ~hits
    free = busy_until(arr, jnp.full(arr.shape, miss_occ_ns, arr.dtype),
                      active=miss, init=0)
    start = free - miss_occ_ns                  # fill-stage grant per miss
    lat = jnp.where(hits, hit_ns,
                    start - arr + miss_ns + jnp.where(evicts, wb_ns, 0))
    return lat.astype(arr.dtype)


@functools.partial(jax.jit, static_argnames=(
    "num_sets", "ways", "policy", "chunk", "interpret", "outstanding",
    "issue_ns", "hit_ns", "miss_ns", "miss_occ_ns", "wb_ns"))
def cache_sim_fused(pages: jnp.ndarray, writes: jnp.ndarray, *, num_sets: int,
                    ways: int, policy: str = "lru", outstanding: int = 32,
                    issue_ns: int = 1, hit_ns: int = 50, miss_ns: int = 5000,
                    miss_occ_ns: int = 213, wb_ns: int = 0, chunk: int = 1024,
                    interpret: bool = True):
    """Fused trace replay: one kernel emits (hits, dirty_evicts, latency_ns,
    arrival_ns).

    Hit/evict decisions are bit-identical to :func:`cache_sim` (and so to
    the lax.scan oracle and the Python policy objects).  Latency model
    (analytic, all in **nanoseconds**; int32 cursors hold ~2.1 s of
    simulated time — callers bound the trace accordingly, see
    :func:`repro.core.replay.pallas_engine.run_pallas`): closed-loop issue
    with ``outstanding`` slots — access *i* arrives ``issue_ns`` after its
    predecessor, but no earlier than completion *i - outstanding* (a ring
    of the last K completion times, i.e. the driver's line-fill-buffer rule
    under in-order completion).  A hit costs ``hit_ns``; a miss queues on
    the fill path's busy-until scalar (``miss_occ_ns`` occupancy per fill —
    the 4 KB cache-DRAM transfer), then costs ``miss_ns`` service, plus
    ``wb_ns`` when it also evicts a dirty page.  The latency state lives in
    SMEM scratch next to the cache state, so trace -> hit/evict/latency is
    one kernel, validated against
    :func:`repro.kernels.ref.cache_sim_fused_ref`."""
    lat = dict(outstanding=max(1, outstanding), issue_ns=issue_ns,
               hit_ns=hit_ns, miss_ns=miss_ns, miss_occ_ns=miss_occ_ns,
               wb_ns=wb_ns)
    hits, evicts, lat_ns, arr_ns = _replay(
        pages, writes, num_sets=num_sets, ways=ways, policy=policy,
        chunk=chunk, interpret=interpret, lat=lat)
    return hits.astype(bool), evicts.astype(bool), lat_ns, arr_ns
