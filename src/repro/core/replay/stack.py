"""Stackable device-state layer: the stateful media/flash machinery of the
fused replay, factored out of the single-host scan body so that *any* number
of hosts can stack private state over shared state.

One lane of state = one Python device object's mutable fields, as pytrees:

* **media state** — the per-device front end (DRAM busy-until, PMEM open
  row, the CXL-SSD page-register file, or the full DRAM-cache layer: packed
  LRU/FIFO/direct frames, MSHR table, writeback buffer, cache-DRAM
  busy-until).  Private per mounted device — per host in mount mode, per
  pool device in pool mode.
* **flash state** — the SimpleSSD backend (FTL mapping + write pointer +
  free-block pool, PAL die/channel occupancy).  Shared by every front end
  built over the same :class:`~repro.core.ssd.hil.HIL`, so pooled-flash
  scenarios (per-host caches over one flash array) contend on the same
  busy-until state the interpreted path does.

The public surface is host-stackable:

* :func:`init_state`\\ ``(cfg, n_hosts, n_flash)`` — state pytrees with a
  leading host (media) / flash-instance axis;
* :func:`step`\\ ``(cfg, p, state, access) -> (state, out)`` — one access
  against lane ``access["lane"]`` / ``access["flash_lane"]``, returning the
  completion tick plus hit/evict flags.

:class:`~repro.core.replay.engine.ReplayEngine` consumes it at ``H=1``
(statically sliced, so the compiled program is the old single-host body),
:class:`~repro.core.replay.multihost.MultiHostReplay` at ``H=N`` with
per-access lane gather/scatter.  Every step function mirrors the interpreted
device *operation for operation* — see :mod:`repro.core.replay.engine` for
the tick-identity contract and the XLA:CPU packing notes.

Garbage collection: when the spec layer decides a trace could outrun the
log-append headroom (``StackConfig.gc``), the flash state grows the full
FTL bookkeeping (``p2l`` inverse map, per-block valid counts, FIFO
free-block queue) and block allocation gains a greedy-GC step — victim
select (fewest valid pages, ties low, matching ``min``/``argmin``), valid
pages migrated as a masked read+program loop, erase, victim appended to the
free queue — mirroring :meth:`repro.core.ssd.ftl.FTL._collect` tick for
tick.  A free-pool underrun (the interpreted path raises "FTL out of
space") sets a sticky ``bad`` flag that callers must surface as
:class:`~repro.core.replay.spec.ReplayUnsupported` — certify-or-refuse,
never silent divergence.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.faults import erase_fails_jnp, nand_read_retries_jnp
from repro.core.replay.spec import (
    DRAM,
    PMEM,
    SSD_BUF,
    SSD_CACHE,
    StackConfig,
)
from repro.obs import scopes

# Plain ints: they stay weakly typed so they promote to int64 inside the
# enable_x64 scope (a jnp.int64 built at import time would truncate to int32).
BIG = 1 << 62          # order-infinity that survives additions
FREE = -1              # free-slot sentinel (pages/addresses are >= 0)

# Packed cache-frame layout: stamp-major so argmin == OrderedDict order.
STAMP_SHIFT = 39
PAGE_BITS = 38
PAGE_FIELD = ((1 << PAGE_BITS) - 1) << 1      # bits [38:1]
STAMP_FIELD = -(1 << STAMP_SHIFT)             # bits [63:39] (sign-extended ok)
MAX_PAGE = (1 << PAGE_BITS) - 2               # strict: all-ones is reserved
MAX_ACCESSES = (1 << 23) - 1                  # stamp<<39 must stay positive


def _i64(x):
    return jnp.asarray(x, jnp.int64)


# A TPU has no 64-bit divide: XLA expands each int64 `//` or `%` into some
# 1,700 serial scalar instructions.  The step's static divisors (page size,
# channels, dies, pages per block) are powers of two in the usual flash
# geometries, and for those a floor division is an arithmetic shift and a
# floor modulo a mask, negative dividends included.
def _floordiv(x, d: int):
    """``x // d`` for a static positive ``d``."""
    if d & (d - 1):
        return x // d
    return x >> (d.bit_length() - 1)


def _mod(x, d: int):
    """``x % d`` for a static positive ``d``."""
    if d & (d - 1):
        return x % d
    return x & (d - 1)


# -------------------------------------------------------------- flash (PAL)
@scopes.scoped("flash")
def _pal_read(cfg: StackConfig, p: Dict, f: Dict, t, ppn, en):
    """Mirror of :meth:`PAL._schedule` (read path, program-suspend rule).

    With NAND fault statics (``cfg.faults``) the read charges
    ``1 + retries`` full sense+transfer rounds, keyed on the in-state read
    sequence number — the exact twin of :meth:`PAL.read_page` consulting
    the plan on its ``_rd_seq`` (the sequence only advances on enabled
    reads, like the python path only calls the PAL for real reads)."""
    C, D = cfg.channels, cfg.dies_per_channel
    ch = _mod(ppn, C)
    i = ch * D + _mod(_floordiv(ppn, C), D)
    db, dp, cb = f["die_busy"], f["die_prog"], f["chan_busy"]
    dbi, dpi, cbi = db[i], dp[i], cb[ch]
    read_t, xfer = p["read_t"], p["xfer_page"]
    if cfg.faults:
        retries = nand_read_retries_jnp(cfg.faults, f["rd_seq"])
        rounds = 1 + retries
        read_t = read_t * rounds
        xfer = xfer * rounds
        f = {**f,
             "rd_seq": f["rd_seq"] + jnp.where(en, 1, 0),
             "c_rr": f["c_rr"] + jnp.where(en, retries, 0)}
    ds = jnp.maximum(t, dbi)
    resume = jnp.minimum(dpi, ds + p["sus_t"])
    ds = jnp.where(dpi > ds, resume, ds)
    array_done = ds + read_t
    new_dp = jnp.where(dpi > ds, dpi + read_t, dpi)
    bus_start = jnp.maximum(array_done, cbi)
    done = bus_start + xfer
    f = {**f,
         "die_busy": db.at[i].set(jnp.where(en, done, dbi)),
         "die_prog": dp.at[i].set(jnp.where(en, new_dp, dpi)),
         "chan_busy": cb.at[ch].set(jnp.where(en, done, cbi))}
    return f, done


@scopes.scoped("flash")
def _pal_prog(cfg: StackConfig, p: Dict, f: Dict, t, ppn, en):
    """Mirror of :meth:`PAL._schedule` (program path: bus in, then array)."""
    C, D = cfg.channels, cfg.dies_per_channel
    ch = _mod(ppn, C)
    i = ch * D + _mod(_floordiv(ppn, C), D)
    db, dp, cb = f["die_busy"], f["die_prog"], f["chan_busy"]
    dbi, dpi, cbi = db[i], dp[i], cb[ch]
    ds = jnp.maximum(jnp.maximum(t, dbi), dpi)
    bus_start = jnp.maximum(ds, cbi)
    bus_done = bus_start + p["xfer_page"]
    done = bus_done + p["prog_t"]
    f = {**f,
         "die_busy": db.at[i].set(jnp.where(en, bus_done, dbi)),
         "die_prog": dp.at[i].set(jnp.where(en, done, dpi)),
         "chan_busy": cb.at[ch].set(jnp.where(en, bus_done, cbi))}
    return f, done


@scopes.scoped("flash")
def _pal_erase(cfg: StackConfig, p: Dict, f: Dict, t, ppn, en):
    """Mirror of :meth:`PAL.erase_block` (array-only, program waits out)."""
    C, D = cfg.channels, cfg.dies_per_channel
    ch = _mod(ppn, C)
    i = ch * D + _mod(_floordiv(ppn, C), D)
    dbi = f["die_busy"][i]
    start = jnp.maximum(jnp.maximum(t, dbi), f["die_prog"][i])
    done = start + p["erase_t"]
    f = {**f, "die_busy": f["die_busy"].at[i].set(jnp.where(en, done, dbi))}
    return f, done


# ----------------------------------------------------- FTL free-block FIFO
def _free_pop(cfg: StackConfig, f: Dict, en):
    """``free_blocks.pop(0)``; an empty pool sets the sticky ``bad`` flag
    (the interpreted FTL raises "out of space" there)."""
    nb = cfg.num_blocks
    head, cnt, q = f["fq_head"], f["fq_count"], f["free_q"]
    v = q[head]
    fm = f["free_mask"]
    f = {**f,
         "fq_head": jnp.where(en, (head + 1) % nb, head),
         "fq_count": jnp.where(en, cnt - 1, cnt),
         "free_mask": fm.at[v].set(jnp.where(en, False, fm[v])),
         "bad": f["bad"] | (en & (cnt <= 0))}
    return f, _i64(v)


def _free_append(cfg: StackConfig, f: Dict, v, en):
    """``free_blocks.append(v)`` (erased victims re-enter at the back)."""
    nb = cfg.num_blocks
    head, cnt, q = f["fq_head"], f["fq_count"], f["free_q"]
    pos = (head + cnt) % nb
    fm = f["free_mask"]
    return {**f,
            "free_q": q.at[pos].set(jnp.where(en, v.astype(q.dtype), q[pos])),
            "fq_count": jnp.where(en, cnt + 1, cnt),
            "free_mask": fm.at[v].set(jnp.where(en, True, fm[v]))}


# -------------------------------------------------------------- FTL + GC
@scopes.scoped("flash")
def _collect(cfg: StackConfig, p: Dict, f: Dict, now):
    """Mirror of :meth:`FTL._collect`: greedy victim (fewest valid pages,
    excluding the write block and free blocks, ties to the lowest block id),
    valid pages migrated read+program in offset order on a serial tick
    chain, erase, victim appended to the free pool.  Runs under a
    :func:`jax.lax.cond`, so non-GC allocations pay nothing."""
    nb, ppb = cfg.num_blocks, cfg.pages_per_block
    cand = (jnp.arange(nb) != f["wpb"]) & (~f["free_mask"])
    if cfg.faults:
        # grown bad blocks never re-enter candidacy (FTL.retired_blocks)
        cand = cand & (~f["rtr_mask"])
    any_cand = cand.any()
    score = jnp.where(cand, f["valid"], jnp.asarray(2**31 - 1, jnp.int32))
    victim = jnp.argmin(score)               # ties -> lowest block id
    base = victim * ppb

    def body(off, carry):
        f, t = carry
        ppn = base + off
        lpn = f["p2l"][ppn]
        live = any_cand & (lpn >= 0)
        f, rdone = _pal_read(cfg, p, f, t, ppn, live)
        t = jnp.where(live, rdone, t)
        # _next_ppn(t, allow_gc=False): migration draws straight from the
        # watermark-reserved pool, never re-entering GC
        need = f["wpp"] >= ppb
        f, v = _free_pop(cfg, f, live & need)
        wpb = jnp.where(need, v, f["wpb"])
        wpp = jnp.where(need, 0, f["wpp"])
        new_ppn = wpb * ppb + wpp
        f = {**f,
             "wpb": jnp.where(live, wpb, f["wpb"]),
             "wpp": jnp.where(live, wpp + 1, f["wpp"])}
        f, pdone = _pal_prog(cfg, p, f, t, new_ppn, live)
        t = jnp.where(live, pdone, t)
        # p2l.pop(ppn); l2p[lpn] = new_ppn; p2l[new_ppn] = lpn; valid moves
        lsafe = jnp.maximum(lpn, 0)
        p2l = f["p2l"].at[ppn].set(jnp.where(live, FREE, f["p2l"][ppn]))
        p2l = p2l.at[new_ppn].set(jnp.where(live, lpn, p2l[new_ppn]))
        l2p = f["l2p"].at[lsafe].set(
            jnp.where(live, new_ppn.astype(jnp.int32), f["l2p"][lsafe]))
        valid = f["valid"].at[_floordiv(new_ppn, ppb)].add(
            jnp.where(live, 1, 0))
        valid = valid.at[victim].add(jnp.where(live, -1, 0))
        f = {**f, "p2l": p2l, "l2p": l2p, "valid": valid}
        if "c_gw" in f:
            f = {**f, "c_gw": f["c_gw"] + jnp.where(live, 1, 0)}
        return f, t

    f, t = jax.lax.fori_loop(0, ppb, body, (f, now))
    f, edone = _pal_erase(cfg, p, f, t, base, any_cand)
    t = jnp.where(any_cand, edone, t)
    if "c_ge" in f:
        # python bumps gc_erases only when a victim existed (the
        # no-candidate early return skips the erase)
        f = {**f, "c_ge": f["c_ge"] + jnp.where(any_cand, 1, 0)}
    fail = jnp.zeros((), bool)
    if cfg.faults:
        # mirror of FTL._collect's erase-fail consult: a failed erase
        # retires the victim (it never returns to the free pool); the
        # erase sequence advances exactly when the python one does (a
        # victim existed — the no-candidate early return skips both)
        fail = any_cand & erase_fails_jnp(cfg.faults, f["er_seq"])
        rtr = f["rtr_mask"]
        f = {**f,
             "er_seq": f["er_seq"] + jnp.where(any_cand, 1, 0),
             "rtr_mask": rtr.at[victim].set(rtr[victim] | fail),
             "c_rb": f["c_rb"] + jnp.where(fail, 1, 0)}
    return _free_append(cfg, f, victim, any_cand & ~fail), t


def _ftl_invalidate(cfg: StackConfig, f: Dict, lpn, en):
    """Mirror of :meth:`FTL._invalidate` (valid-count + inverse-map upkeep —
    only tracked on GC-capable stacks, where it decides victims)."""
    old = f["l2p"][lpn]
    has = en & (old >= 0)
    osafe = jnp.maximum(old, 0)
    return {**f,
            "valid": f["valid"].at[
                _floordiv(old, cfg.pages_per_block)].add(
                    jnp.where(has, -1, 0)),
            "p2l": f["p2l"].at[osafe].set(
                jnp.where(has, FREE, f["p2l"][osafe]))}


@scopes.scoped("flash")
def _alloc_ppn(cfg: StackConfig, p: Dict, f: Dict, t, en):
    """Mirror of :meth:`FTL._next_ppn`: returns ``(f, ppn, gc_done)``."""
    need = f["wpp"] >= cfg.pages_per_block
    if not cfg.gc:
        # log-append lane: the free pool is a pristine counter (spec-time
        # headroom check guarantees GC can never trigger)
        wpb = jnp.where(need, f["nfree"], f["wpb"])
        nfree = jnp.where(need, f["nfree"] + 1, f["nfree"])
        wpp = jnp.where(need, 0, f["wpp"])
        ppn = wpb * cfg.pages_per_block + wpp
        f = {**f,
             "wpb": jnp.where(en, wpb, f["wpb"]),
             "nfree": jnp.where(en, nfree, f["nfree"]),
             "wpp": jnp.where(en, wpp + 1, f["wpp"])}
        return f, ppn, t
    trigger = en & need & (f["fq_count"] <= cfg.gc_watermark_blocks)
    f = {**f, "gcs": f["gcs"] + jnp.where(trigger, 1, 0)}
    f, gc_done = jax.lax.cond(
        trigger,
        lambda op: _collect(cfg, p, op[0], op[1]),
        lambda op: op,
        (f, t))
    f, v = _free_pop(cfg, f, en & need)
    wpb = jnp.where(need, v, f["wpb"])
    wpp = jnp.where(need, 0, f["wpp"])
    ppn = wpb * cfg.pages_per_block + wpp
    f = {**f,
         "wpb": jnp.where(en, wpb, f["wpb"]),
         "wpp": jnp.where(en, wpp + 1, f["wpp"])}
    return f, ppn, jnp.where(en, gc_done, t)


@scopes.scoped("flash")
def _hil_write(cfg: StackConfig, p: Dict, f: Dict, t, lpn, en):
    """HIL overhead + FTL write: invalidate (GC stacks), allocate — running
    greedy GC when the free pool is at the watermark — then program."""
    t0 = t + p["hil_ov"]
    if "c_hw" in f:
        f = {**f, "c_hw": f["c_hw"] + jnp.where(en, 1, 0)}
    if cfg.gc:
        f = _ftl_invalidate(cfg, f, lpn, en)
    f, ppn, t1 = _alloc_ppn(cfg, p, f, t0, en)
    f = {**f,
         "l2p": f["l2p"].at[lpn].set(
             jnp.where(en, ppn.astype(jnp.int32), f["l2p"][lpn]))}
    if cfg.gc:
        f = {**f,
             "p2l": f["p2l"].at[ppn].set(
                 jnp.where(en, lpn.astype(jnp.int32), f["p2l"][ppn])),
             "valid": f["valid"].at[
                 _floordiv(ppn, cfg.pages_per_block)].add(
                     jnp.where(en, 1, 0))}
    return _pal_prog(cfg, p, f, t1, ppn, en)


@scopes.scoped("flash")
def _hil_read(cfg: StackConfig, p: Dict, f: Dict, t, ppn, en):
    """HIL overhead + FTL read of a programmed page (callers check the
    mapping table first, exactly like the cache's ``is_written`` gate)."""
    if "c_hr" in f:
        f = {**f, "c_hr": f["c_hr"] + jnp.where(en, 1, 0)}
    return _pal_read(cfg, p, f, t + p["hil_ov"], jnp.maximum(ppn, 0), en)


# ------------------------------------------------------------- device steps
def _dram_step(cfg: StackConfig, p: Dict, md: Dict, f, t, addr, wr, posted,
               ctr):
    start = jnp.maximum(t, md["busy"])
    occ_done = start + p["occ"]
    done = occ_done + jnp.where(posted, p["pack"], p["load"])
    md = {**md, "busy": occ_done}
    return md, f, done, {}


def _pmem_step(cfg: StackConfig, p: Dict, md: Dict, f, t, addr, wr, posted,
               ctr):
    row = addr // p["row_bytes"]
    row_hit = row == md["row"]
    lat = p["lat"][jnp.where(wr, 1, 0), jnp.where(row_hit, 1, 0)]
    start = jnp.maximum(t, md["busy"])
    occ_done = start + p["occ"]
    done = occ_done + jnp.where(posted, p["pack"], lat)
    md = {**md, "busy": occ_done, "row": row}
    return md, f, done, {"hit": row_hit}


def _buf_step(cfg: StackConfig, p: Dict, md: Dict, f: Dict, t, addr, wr,
              posted, ctr):
    """CXL-SSD page-register buffer: LRU over a handful of open pages;
    misses amplify to 4 KB flash ops (read-modify-write for writes)."""
    page = _floordiv(addr, cfg.page_bytes)
    frames = md["frames"]
    pfield = page << 1
    match = (frames & PAGE_FIELD) == pfield
    match = match & (frames >= 0)
    fidx = jnp.argmax(match)
    hit = match[fidx]
    miss = ~hit
    old = frames[fidx]

    def miss_fn(op):
        frames, f = op
        vic = jnp.argmin(frames)
        vval = frames[vic]
        ev_dirty = (vval >= 0) & ((vval & 1) > 0)
        ev_page = (vval & PAGE_FIELD) >> 1
        ppn = f["l2p"][page]
        was_written = ppn >= 0
        f, rdone = _hil_read(cfg, p, f, t, _i64(ppn), was_written)
        done0 = jnp.where(was_written, rdone, t)
        f, _ = _hil_write(cfg, p, f, done0, ev_page, ev_dirty)
        return f, done0, vic, ev_dirty, was_written

    def hit_fn(op):
        frames, f = op
        false = jnp.zeros((), bool)
        return f, t, fidx, false, false

    f, done0, vic, flushed, filled = jax.lax.cond(
        miss, miss_fn, hit_fn, (frames, f))

    # single commit: LRU touch on hit, insert over the victim on miss
    touch_val = (ctr << STAMP_SHIFT) | pfield | ((old & 1) | wr)
    insert_val = (ctr << STAMP_SHIFT) | pfield | wr
    idx = jnp.where(miss, vic, fidx)
    val = jnp.where(miss, insert_val, touch_val)
    frames = frames.at[idx].set(val)

    done = done0 + p["internal"]
    md = {**md, "frames": frames}
    return md, f, done, {"hit": hit, "evict": flushed, "fill": filled}


def _cache_step(cfg: StackConfig, p: Dict, md: Dict, f: Dict, t, addr, wr,
                posted, ctr):
    """The paper's DRAM cache layer, one access: MSHR coalesce -> resident
    hit -> miss (MSHR stall, evict + writeback queue, flash fill).  Mirrors
    :meth:`repro.core.cache.dram_cache.DRAMCache.access` branch for branch."""
    page = _floordiv(addr, cfg.page_bytes)
    frames = md["frames"]
    pfield = page << 1

    # ---- MSHR lookup (in-flight fill rides the existing SSD read)
    mm = md["mpage"] == page
    m_idx = jnp.argmax(mm)
    m_exists = mm[m_idx]
    m_ready = md["mready"][m_idx]
    coalesce = m_exists & (m_ready > t)

    # ---- residency
    if cfg.cache_assoc:
        match = ((frames & PAGE_FIELD) == pfield) & (frames >= 0)
        fidx = jnp.argmax(match)
        resident = match[fidx]
    else:
        fidx = page % p["cap"]
        fv = frames[fidx]
        resident = (fv >= 0) & ((fv & PAGE_FIELD) == pfield)
    hit = (~coalesce) & resident
    miss = (~coalesce) & (~resident)
    old = frames[fidx]

    # ---- hit: 64 B transfer occupies cache-DRAM bandwidth
    xstart = jnp.maximum(t, md["dram_busy"])
    xdone = xstart + p["line_xfer"]

    # ---- miss machinery behind one cond (hits pass the buffers through)
    def miss_fn(op):
        frames, mpage, mready, wtick, f = op
        # MSHR allocate (stall if the table is full)
        mfull = jnp.sum(mpage >= 0) >= cfg.mshr_entries
        vic_ready = jnp.min(mready)             # free slots hold BIG
        start1 = jnp.where(mfull, jnp.maximum(t, vic_ready), t)
        kill = mfull & (mready <= vic_ready)
        mpage = jnp.where(kill, FREE, mpage)
        mready = jnp.where(kill, BIG, mready)
        # write-allocate insert: victim = argmin of packed stamps (invalid
        # frames are -1, below every valid packed value)
        vic = jnp.argmin(frames) if cfg.cache_assoc else fidx
        vval = frames[vic]
        ev_valid = vval >= 0
        ev_page = (vval & PAGE_FIELD) >> 1
        do_wb = ev_valid & ((vval & 1) > 0)
        # writeback queue: background flash write, stall only if full.
        # Mutations are gated on do_wb — Python touches the queue only via
        # _queue_writeback, which clean misses never call.
        dead = wtick <= start1                   # reap(now)
        wtick = jnp.where(do_wb & dead, FREE, wtick)
        wfull = jnp.sum(~dead) >= cfg.wb_slots
        wmin = jnp.min(jnp.where(dead, BIG, wtick))
        stall = jnp.where(wfull, wmin, start1)
        wtick = jnp.where(do_wb & wfull & (wtick <= stall), FREE, wtick)
        f, wdone = _hil_write(cfg, p, f, stall, ev_page, do_wb)
        wslot = jnp.argmin(wtick)
        wtick = wtick.at[wslot].set(jnp.where(do_wb, wdone, wtick[wslot]))
        start2 = jnp.where(do_wb, jnp.maximum(start1, stall), start1)
        # fill from flash (virgin pages skip the read), then cache-DRAM
        ppn = f["l2p"][page]
        was_written = ppn >= 0
        f, rdone = _hil_read(cfg, p, f, start2, _i64(ppn), was_written)
        flash_done = jnp.where(was_written, rdone, start2)
        fill_done = jnp.maximum(flash_done, md["dram_busy"]) + p["page_xfer"]
        # MSHR insert (dict semantics: existing key overwrites) + expiry
        slot = jnp.where(m_exists, m_idx, jnp.argmin(mpage))
        mpage = mpage.at[slot].set(page)
        mready = mready.at[slot].set(fill_done)
        kill2 = mready <= t
        mpage = jnp.where(kill2, FREE, mpage)
        mready = jnp.where(kill2, BIG, mready)
        return (mpage, mready, wtick, f, start2, fill_done, vic, do_wb,
                mfull, ev_valid)

    def pass_fn(op):
        frames, mpage, mready, wtick, f = op
        false = jnp.zeros((), bool)
        return (mpage, mready, wtick, f, t, t, fidx, false, false, false)

    (mpage, mready, wtick, f, start2, fill_done, vic, do_wb, stalled,
     evicted) = jax.lax.cond(
        miss, miss_fn, pass_fn,
        (frames, md["mpage"], md["mready"], md["wtick"], f))

    # ---- single frame commit: touch (hit / coalesced store) or insert
    touch_en = (coalesce & wr & resident) | hit
    stamp_bits = jnp.where(p["is_lru"], ctr << STAMP_SHIFT, old & STAMP_FIELD)
    touch_val = stamp_bits | pfield | ((old & 1) | wr)
    insert_val = (ctr << STAMP_SHIFT) | pfield | wr
    idx = jnp.where(miss, vic, fidx)
    val = jnp.where(miss, insert_val, jnp.where(touch_en, touch_val, old))
    frames = frames.at[idx].set(val)

    dram_busy = jnp.where(hit, xdone,
                          jnp.where(miss, fill_done, md["dram_busy"]))
    ret_co = jnp.where(wr, t + p["hit_lat"], m_ready + p["hit_lat"])
    ret_hit = jnp.where(wr,
                        jnp.where(posted, t + p["pack10"], t + p["hit_lat"]),
                        jnp.maximum(xdone, t + p["hit_lat"]))
    ret_miss = jnp.where(wr, start2 + p["hit_lat"], fill_done + p["hit_lat"])
    ret = jnp.where(coalesce, ret_co, jnp.where(hit, ret_hit, ret_miss))

    md = {**md, "frames": frames, "mpage": mpage, "mready": mready,
          "wtick": wtick, "dram_busy": dram_busy}
    return md, f, jnp.maximum(t, ret), {
        "hit": hit, "evict": do_wb, "miss": miss, "coalesce": coalesce,
        "stall": stalled, "eviction": evicted}


_STEPS = {DRAM: _dram_step, PMEM: _pmem_step, SSD_BUF: _buf_step,
          SSD_CACHE: _cache_step}

# media kinds whose state splits into a private front end + a flash backend
FLASH_KINDS = (SSD_BUF, SSD_CACHE)


def has_flash(cfg: StackConfig) -> bool:
    return cfg.kind in FLASH_KINDS


# -------------------------------------------------------------- state init
def flash_init(cfg: StackConfig) -> Dict:
    """One flash instance's state (one :class:`HIL`: FTL map + write pointer
    + free pool, PAL die/channel busy-until)."""
    C, D = cfg.channels, cfg.dies_per_channel
    f = {
        "l2p": jnp.full(cfg.num_pages, -1, jnp.int32),
        "wpb": _i64(0), "wpp": _i64(0),
        "die_busy": jnp.zeros(C * D, jnp.int64),
        "die_prog": jnp.zeros(C * D, jnp.int64),
        "chan_busy": jnp.zeros(C, jnp.int64),
    }
    if cfg.gc:
        nb = cfg.num_blocks
        f.update({
            # free_blocks = deque(1..nb-1): slot nb-1 is initially unused
            "free_q": jnp.where(jnp.arange(nb) < nb - 1,
                                jnp.arange(nb) + 1, 0).astype(jnp.int32),
            "fq_head": _i64(0),
            "fq_count": _i64(nb - 1),
            "free_mask": jnp.arange(nb) >= 1,
            "p2l": jnp.full(nb * cfg.pages_per_block, FREE, jnp.int32),
            "valid": jnp.zeros(nb, jnp.int32),
            "gcs": _i64(0),
            "bad": jnp.zeros((), bool),
        })
    else:
        f["nfree"] = _i64(1)
    if cfg.faults:
        # deterministic NAND faults: in-state read/erase sequence numbers
        # (the PAL/FTL twins), retry/retirement totals, retired-block mask
        f["rd_seq"] = _i64(0)
        f["c_rr"] = _i64(0)
        if cfg.gc:
            f["er_seq"] = _i64(0)
            f["rtr_mask"] = jnp.zeros(cfg.num_blocks, bool)
            f["c_rb"] = _i64(0)
    if cfg.counters:
        # FTL.stats twins (host vs GC traffic); gc_runs rides on "gcs"
        f["c_hr"] = _i64(0)
        f["c_hw"] = _i64(0)
        if cfg.gc:
            f["c_gw"] = _i64(0)
            f["c_ge"] = _i64(0)
    return f


def media_init(cfg: StackConfig) -> Dict:
    """One front end's private state (no flash — see :func:`flash_init`)."""
    if cfg.kind == DRAM:
        return {"busy": _i64(0)}
    if cfg.kind == PMEM:
        return {"busy": _i64(0), "row": _i64(-1)}
    if cfg.kind == SSD_BUF:
        return {"frames": jnp.full(cfg.buf_entries, -1, jnp.int64)}
    if cfg.kind == SSD_CACHE:
        return {"frames": jnp.full(cfg.cache_frames, -1, jnp.int64),
                "mpage": jnp.full(cfg.mshr_entries, FREE, jnp.int64),
                "mready": jnp.full(cfg.mshr_entries, BIG, jnp.int64),
                "wtick": jnp.full(cfg.wb_slots, FREE, jnp.int64),
                "dram_busy": _i64(0)}
    raise ValueError(cfg.kind)


def media_step(cfg: StackConfig, p: Dict, md: Dict, f: Optional[Dict], t,
               addr, wr, posted, ctr):
    """One access against one unstacked (media, flash) lane pair.  Returns
    ``(md, f, done, extras)`` where ``extras`` is a per-kind dict of event
    flags (``hit``/``evict``/``miss``/``coalesce``/``stall``/...) feeding
    :func:`repro.core.replay.metrics.media_increments`; ``f`` passes
    through untouched for flash-less kinds."""
    return _STEPS[cfg.kind](cfg, p, md, f, t, addr, wr, posted, ctr)


# ------------------------------------------------------- stacked interface
def init_state(cfg: StackConfig, n_hosts: int = 1,
               n_flash: Optional[int] = None) -> Dict:
    """State pytrees with a leading lane axis: ``media`` gets ``n_hosts``
    private lanes, ``flash`` gets ``n_flash`` instances (default: one per
    host; irrelevant for flash-less kinds).  ``n_flash < n_hosts`` is the
    pooled-flash shape: several private front ends over shared FTL/PAL."""
    if n_flash is None:
        n_flash = n_hosts
    media = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[media_init(cfg) for _ in range(n_hosts)])
    flash = None
    if has_flash(cfg):
        flash = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[flash_init(cfg) for _ in range(n_flash)])
    return {"media": media, "flash": flash}


def _n_lanes(tree) -> int:
    return jax.tree.leaves(tree)[0].shape[0]


@scopes.scoped("media")
def step(cfg: StackConfig, p: Dict, state: Dict, access: Dict
         ) -> Tuple[Dict, Dict]:
    """One access against the stacked state.

    ``access`` keys: ``lane`` (media lane), ``flash_lane``, ``t`` (arrival
    tick after transport), ``addr``, ``write``, ``posted``, ``ctr`` (global
    monotone stamp).  Returns ``(state, out)`` with ``out`` carrying
    ``done`` (completion tick) and ``hit``/``evict`` flags.

    An optional ``en`` key (scalar bool) gates the lane *writeback*: when
    false the step still executes — every SPMD replica of a sharded replay
    runs the same program — but the lane state is left untouched, so only
    the shard that owns the issuing host commits the mutation.  Callers
    gating with ``en`` must also gate every use of ``out`` (``done`` and the
    event flags are garbage on a disabled step).

    With one lane the gather/scatter degenerates to static slicing, so the
    compiled single-host program is exactly the pre-refactor scan body.
    """
    media, flash = state["media"], state["flash"]
    en = access.get("en")
    single = _n_lanes(media) == 1
    lane = 0 if single else access["lane"]
    md = jax.tree.map(lambda x: x[lane], media)
    f = None
    if flash is not None:
        fsingle = _n_lanes(flash) == 1
        flane = 0 if fsingle else access["flash_lane"]
        f = jax.tree.map(lambda x: x[flane], flash)
    md, f, done, ex = media_step(
        cfg, p, md, f, access["t"], access["addr"], access["write"],
        access["posted"], access["ctr"])
    if en is None:
        wb = lambda full, v, i: full.at[i].set(v)
    else:
        wb = lambda full, v, i: full.at[i].set(jnp.where(en, v, full[i]))
    media = jax.tree.map(lambda full, v: wb(full, v, lane), media, md)
    if flash is not None:
        flash = jax.tree.map(lambda full, v: wb(full, v, flane), flash, f)
    false = jnp.zeros((), bool)
    return ({"media": media, "flash": flash},
            {**ex, "done": done, "hit": ex.get("hit", false),
             "evict": ex.get("evict", false)})


def flash_health(state: Dict) -> Tuple[object, object]:
    """``(bad_any, gc_total)`` across every flash lane — ``bad_any`` is the
    sticky certify-or-refuse bit, ``gc_total`` the GC-run counter (both
    zero-shaped constants for flash-less or log-append stacks)."""
    flash = state["flash"]
    if flash is None or "bad" not in flash:
        return jnp.zeros((), bool), _i64(0)
    return flash["bad"].any(), flash["gcs"].sum()


def flash_counters(state: Dict):
    """Per-flash-lane :data:`~repro.core.replay.metrics.FLASH_COUNTERS`
    snapshot, ``(n_flash, 5)`` int64 — ``None`` when the stack carries no
    counters (``StackConfig.counters=False``) or no flash at all."""
    flash = state["flash"]
    if flash is None or "c_hr" not in flash:
        return None
    z = jnp.zeros_like(flash["c_hr"])
    return jnp.stack([flash["c_hr"], flash["c_hw"],
                      flash.get("c_gw", z), flash.get("c_ge", z),
                      flash.get("gcs", z)], axis=-1)


def fault_counters(state: Dict):
    """``(nand_read_retries, retired_blocks)`` totals across every flash
    lane — kept out of :func:`flash_counters` so the pinned (n, 5) metrics
    shape is untouched; both zero for stacks built without fault statics."""
    flash = state["flash"]
    if flash is None or "c_rr" not in flash:
        return _i64(0), _i64(0)
    retired = flash["c_rb"].sum() if "c_rb" in flash else _i64(0)
    return flash["c_rr"].sum(), retired
