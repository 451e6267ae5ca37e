"""Plain reference for the benchmark's deployments: one access at a time.

A straightforward interpreter of the simulated memory system that a
configuration file under ``bench/configs`` describes, written for the
benchmark and importing nothing of the program.  It follows the semantics
of the paper's device model (CXL-SSD-Sim, arXiv:2501.02524, Table I) as
the interpreted drivers state them:

* a host issues its trace through ``outstanding`` line-fill-buffer slots
  (issue at ``max(clock, earliest free slot)``, clock advances by the issue
  overhead); several hosts interleave in global issue-time order, ties to
  the lower host index;
* transport is a point-to-point CXL link (serialization busy-until plus the
  round-trip extra) or a routed switch fabric (per-port busy-until,
  store-and-forward latency at every switch, the round-trip extra once);
* the cached CXL-SSD: a write-back, write-allocate DRAM page cache (LRU or
  FIFO), an MSHR table that coalesces accesses to an in-flight page and
  stalls when full, a bounded writeback buffer, cache-DRAM bandwidth;
  behind it the HIL overhead, a page-mapped FTL with greedy GC, and NAND
  dies and channels with busy-untils and program suspend.

Ticks are picoseconds.  ``tick_bits=32`` stores every tick as a signed
32-bit integer (wrapping), the lower precision the benchmark's control
runs at; the default keeps Python's unbounded integers, which the
configurations' int64 ticks never exceed.

:func:`replay` returns per-host latencies, the job summary and a metrics
dict in the schema of the program's metrics bundle.
"""

from __future__ import annotations

import heapq
import math
from collections import OrderedDict

LINE = 64
PAGE = 4096


def ns(x: float) -> int:
    return int(round(x * 1_000))


def us(x: float) -> int:
    return int(round(x * 1_000_000))


def _ident(x: int) -> int:
    return x


def _wrap32(x: int) -> int:
    return ((x + (1 << 31)) & 0xFFFFFFFF) - (1 << 31)


# ------------------------------------------------------------------ NAND
class Nand:
    """Dies and channels of the flash array, busy-until per resource."""

    def __init__(self, ssd: dict, w):
        t = ssd["nand"]
        self.w = w
        self.channels = ssd["channels"]
        self.dies = ssd["dies_per_channel"]
        self.page_bytes = ssd["page_bytes"]
        self.read_t = us(t["t_read_us"])
        self.prog_t = us(t["t_prog_us"])
        self.erase_t = us(t["t_erase_us"])
        self.suspend_t = us(t["t_suspend_us"])
        self.xfer = ns(self.page_bytes / t["channel_mbps"] * 1e3)
        n = self.channels * self.dies
        self.busy = [0] * n
        self.prog_until = [0] * n
        self.ch_busy = [0] * self.channels

    def _where(self, ppn: int):
        ch = ppn % self.channels
        die = (ppn // self.channels) % self.dies
        return ch, ch * self.dies + die

    def read(self, now: int, ppn: int) -> int:
        w = self.w
        ch, d = self._where(ppn)
        start = max(now, self.busy[d])
        if self.prog_until[d] > start:
            start = min(self.prog_until[d], w(start + self.suspend_t))
        array_done = w(start + self.read_t)
        if self.prog_until[d] > start:
            self.prog_until[d] = w(self.prog_until[d] + self.read_t)
        done = w(max(array_done, self.ch_busy[ch]) + self.xfer)
        self.ch_busy[ch] = done
        self.busy[d] = done
        return done

    def program(self, now: int, ppn: int) -> int:
        w = self.w
        ch, d = self._where(ppn)
        die_start = max(now, self.busy[d], self.prog_until[d])
        bus_done = w(max(die_start, self.ch_busy[ch]) + self.xfer)
        done = w(bus_done + self.prog_t)
        self.ch_busy[ch] = bus_done
        self.busy[d] = bus_done
        self.prog_until[d] = done
        return done

    def erase(self, now: int, ppn: int) -> int:
        _, d = self._where(ppn)
        done = self.w(max(now, self.busy[d], self.prog_until[d])
                      + self.erase_t)
        self.busy[d] = done
        return done


# ------------------------------------------------------------------- FTL
class Flash:
    """HIL + page-mapped FTL with greedy GC over the NAND array."""

    def __init__(self, ssd: dict, w):
        self.w = w
        self.nand = Nand(ssd, w)
        self.ppb = ssd["pages_per_block"]
        self.overhead = ns(ssd["hil_overhead_ns"])
        logical = ssd["capacity_bytes"] // ssd["page_bytes"]
        phys = int(logical * (1 + ssd["op_ratio"]))
        self.num_blocks = max(4, (phys + self.ppb - 1) // self.ppb)
        self.watermark = max(2, int(self.num_blocks * ssd["gc_watermark"]))
        self.l2p: dict = {}
        self.p2l: dict = {}
        self.valid = [0] * self.num_blocks
        self.wp_block = 0
        self.wp_page = 0
        self.free = list(range(1, self.num_blocks))
        self.free_set = set(self.free)
        self.stats = {"host_reads": 0, "host_writes": 0, "gc_writes": 0,
                      "gc_erases": 0, "gc_runs": 0}

    def _alloc(self, now: int, allow_gc: bool = True):
        gc_done = now
        if self.wp_page >= self.ppb:
            if allow_gc and len(self.free) <= self.watermark:
                gc_done = self._collect(now)
            if not self.free:
                raise RuntimeError("flash out of space")
            self.wp_block = self.free.pop(0)
            self.free_set.discard(self.wp_block)
            self.wp_page = 0
        ppn = self.wp_block * self.ppb + self.wp_page
        self.wp_page += 1
        return ppn, gc_done

    def _collect(self, now: int) -> int:
        self.stats["gc_runs"] += 1
        cands = [b for b in range(self.num_blocks)
                 if b != self.wp_block and b not in self.free_set]
        if not cands:
            return now
        victim = min(cands, key=lambda b: self.valid[b])
        t = now
        base = victim * self.ppb
        for ppn in range(base, base + self.ppb):
            lpn = self.p2l.get(ppn)
            if lpn is None:
                continue
            t = self.nand.read(t, ppn)
            new, _ = self._alloc(t, allow_gc=False)
            t = self.nand.program(t, new)
            del self.p2l[ppn]
            self.l2p[lpn] = new
            self.p2l[new] = lpn
            self.valid[new // self.ppb] += 1
            self.valid[victim] -= 1
            self.stats["gc_writes"] += 1
        t = self.nand.erase(t, base)
        self.stats["gc_erases"] += 1
        self.free.append(victim)
        self.free_set.add(victim)
        return t

    def written(self, lpn: int) -> bool:
        return lpn in self.l2p

    def read(self, now: int, lpn: int) -> int:
        t0 = self.w(now + self.overhead)
        self.stats["host_reads"] += 1
        ppn = self.l2p.get(lpn)
        if ppn is None:
            return max(t0, self.w(t0 + self.nand.xfer))
        return max(t0, self.nand.read(t0, ppn))

    def write(self, now: int, lpn: int) -> int:
        t0 = self.w(now + self.overhead)
        self.stats["host_writes"] += 1
        old = self.l2p.get(lpn)
        if old is not None:
            self.valid[old // self.ppb] -= 1
            self.p2l.pop(old, None)
        ppn, t = self._alloc(t0)
        done = self.nand.program(t, ppn)
        self.l2p[lpn] = ppn
        self.p2l[ppn] = lpn
        self.valid[ppn // self.ppb] += 1
        return max(t0, done)


# ------------------------------------------------------------ DRAM cache
class CachedSSD:
    """The cached CXL-SSD's media: DRAM page cache in front of flash."""

    def __init__(self, cache: dict, flash: Flash, w):
        self.w = w
        self.flash = flash
        self.frames = cache["capacity_bytes"] // PAGE
        self.lru = cache["policy"] == "lru"
        if cache["policy"] not in ("lru", "fifo"):
            raise ValueError(f"policy {cache['policy']!r} not modelled here")
        self.hit_t = ns(cache["hit_latency_ns"])
        self.per_byte_ns = 1.0 / cache["dram_bw_gbps"]
        self.mshr_entries = cache["mshr_entries"]
        self.wb_slots = cache["writeback_buffer"]
        self.resident: OrderedDict = OrderedDict()     # page -> dirty
        self.mshr: dict = {}                            # page -> ready tick
        self.wb: list = []
        self.dram_busy = 0
        self.c = dict.fromkeys(
            ("accesses", "reads", "writes", "hits", "misses",
             "mshr_coalesced", "mshr_stalls", "fills", "writebacks",
             "evictions", "dirty_evictions"), 0)

    def _xfer(self, now: int, nbytes: int) -> int:
        done = self.w(max(now, self.dram_busy) + ns(nbytes * self.per_byte_ns))
        self.dram_busy = done
        return done

    def _writeback(self, now: int, page: int) -> int:
        self.wb = [t for t in self.wb if t > now]
        stall = now
        if len(self.wb) >= self.wb_slots:
            stall = min(self.wb)
            self.wb = [t for t in self.wb if t > stall]
        self.wb.append(self.flash.write(stall, page))
        self.c["writebacks"] += 1
        return stall

    def access(self, now: int, addr: int, write: bool, posted: bool):
        """Returns ``(done tick, hit)``."""
        w, c = self.w, self.c
        c["accesses"] += 1
        c["writes" if write else "reads"] += 1
        page = addr // PAGE
        ready = self.mshr.get(page)
        if ready is not None and ready > now:
            c["mshr_coalesced"] += 1
            if write:
                if page in self.resident:
                    self.resident[page] = True
                    if self.lru:
                        self.resident.move_to_end(page)
                return w(now + self.hit_t), False
            return w(max(ready, now) + self.hit_t), False
        if page in self.resident:
            c["hits"] += 1
            self.resident[page] |= write
            if self.lru:
                self.resident.move_to_end(page)
            done = self._xfer(now, LINE)
            if write and posted:
                return w(now + ns(10.0)), True     # posted store accepted
            if write:
                return w(now + self.hit_t), True
            return max(done, w(now + self.hit_t)), True
        c["misses"] += 1
        start = now
        if len(self.mshr) >= self.mshr_entries:
            c["mshr_stalls"] += 1
            first = min(self.mshr.values())
            self.mshr = {p: t for p, t in self.mshr.items() if t > first}
            start = max(start, first)
        if len(self.resident) >= self.frames:
            victim, dirty = self.resident.popitem(last=False)
            c["evictions"] += 1
            if dirty:
                c["dirty_evictions"] += 1
                start = max(start, self._writeback(start, victim))
        self.resident[page] = write
        c["fills"] += 1
        flash_done = (self.flash.read(start, page)
                      if self.flash.written(page) else start)
        fill = self._xfer(flash_done, PAGE)
        self.mshr[page] = fill
        self.mshr = {p: t for p, t in self.mshr.items() if t > now}
        if write:
            return w(max(start, now) + self.hit_t), False
        return w(fill + self.hit_t), False


# ------------------------------------------------------------- transport
class Link:
    """Point-to-point CXL link (``attach: direct``)."""

    def __init__(self, link: dict, w):
        self.w = w
        self.occ = ns(LINE / link["bw_gbps"])
        self.rt = ns(link["rt_extra_ns"])
        self.busy = 0

    def traverse(self, now: int) -> int:
        start = max(now, self.busy)
        self.busy = self.w(start + self.occ)
        return self.w(self.busy + self.rt)


class Port:
    def __init__(self, bw_gbps: float):
        self.occ = ns(LINE / bw_gbps)
        self.busy = 0
        self.packets = 0
        self.queued = 0
        self.by_host: dict = {}


class TwoLevelFabric:
    """``two_level``: hosts round-robin onto leaf switches, the leaves
    uplinked to a root switch that holds every device; host i reaches
    device i over host->leaf, leaf->root and root->device ports."""

    def __init__(self, fab: dict, hosts: int, w):
        self.w = w
        self.fwd = ns(fab["forward_ns"])
        self.rt = ns(fab["rt_extra_ns"])
        bw = fab["bw_gbps"]
        self.ports: dict = {}
        self.paths = []
        for i in range(hosts):
            leaf = f"s{i % fab['num_leaves']}"
            hops = [(f"h{i}", leaf), (leaf, "s_root"), ("s_root", f"d{i}")]
            for key in hops:
                self.ports.setdefault(key, Port(bw))
            self.paths.append(hops)

    def traverse(self, now: int, host: int) -> int:
        t = now
        for u, v in self.paths[host]:
            p = self.ports[(u, v)]
            start = max(t, p.busy)
            p.queued += start - t
            p.busy = self.w(start + p.occ)
            p.packets += 1
            p.by_host[f"h{host}"] = p.by_host.get(f"h{host}", 0) + LINE
            t = p.busy
            if v.startswith("s"):
                t = self.w(t + self.fwd)
        return self.w(t + self.rt)


# ------------------------------------------------------------ the system
class System:
    """One deployment: hosts, transport and one cached CXL-SSD per host."""

    def __init__(self, config: dict, tick_bits: int = 64,
                 cache_override: dict | None = None):
        w = _wrap32 if tick_bits == 32 else _ident
        self.w = w
        self.cfg = config
        dev = config["device"]
        if dev["kind"] != "cxl-ssd-cache":
            raise ValueError(f"device {dev['kind']!r} not modelled here")
        cache = {**dev["cache"], **(cache_override or {})}
        self.hosts = config["hosts"]
        self.media = [CachedSSD(cache, Flash(dev["ssd"], w), w)
                      for _ in range(self.hosts)]
        if config["attach"] == "direct":
            if self.hosts != 1:
                raise ValueError("a direct link serves one host")
            self.link = Link(dev["link"], w)
            self.fabric = None
        elif config["attach"] == "fabric":
            fab = config["fabric"]
            if fab["kind"] != "two_level":
                raise ValueError(f"fabric {fab['kind']!r} not modelled here")
            self.link = None
            self.fabric = TwoLevelFabric(fab, self.hosts, w)
        else:
            raise ValueError(f"attach {config['attach']!r}")

    def service(self, host: int, now: int, addr: int, write: bool,
                posted: bool):
        if self.fabric is not None:
            t = self.fabric.traverse(now, host)
        else:
            t = self.link.traverse(now)
        return self.media[host].access(t, addr, write, posted)


def replay(config: dict, addrs, writes, tick_bits: int = 64,
           cache_override: dict | None = None, metrics: dict | None = None):
    """Replay ``addrs``/``writes`` (one row per host) on a fresh system.

    Returns ``{"latency": [list per host], "summary": [(accesses, bytes,
    elapsed, summed latency, end tick) per host], "elapsed": global span,
    "hit": [cache hit per access, per host], "metrics": dict or None}``; ``metrics`` is the histogram/window shape
    ``{"hist_buckets", "window_ticks", "num_windows"}``."""
    sysm = System(config, tick_bits, cache_override)
    w = sysm.w
    H = sysm.hosts
    out_n = config["outstanding"]
    issue_ov = ns(config["issue_overhead_ns"])
    posted_writes = config["posted_writes"]
    rows = [([int(a) for a in addrs[h]], [bool(x) for x in writes[h]])
            for h in range(H)]
    slots = [[0] * out_n for _ in range(H)]
    clock = [0] * H
    pos = [0] * H
    lat = [[] for _ in range(H)]
    first = [None] * H
    last = [0] * H
    recs = [[] for _ in range(H)]       # (issue, done, hit) per access
    ready = [(0, h) for h in range(H) if rows[h][0]]
    heapq.heapify(ready)
    while ready:
        _, h = heapq.heappop(ready)
        a, wr = rows[h][0][pos[h]], rows[h][1][pos[h]]
        issue = max(clock[h], heapq.heappop(slots[h]))
        if first[h] is None:
            first[h] = issue
        done, hit = sysm.service(h, issue, a, wr, wr and posted_writes)
        heapq.heappush(slots[h], done)
        lat[h].append(w(done - issue))
        recs[h].append((issue, done, hit))
        last[h] = max(last[h], done)
        clock[h] = w(issue + issue_ov)
        pos[h] += 1
        if pos[h] < len(rows[h][0]):
            heapq.heappush(ready, (max(clock[h], slots[h][0]), h))
    summary = [(pos[h], pos[h] * LINE, last[h] - first[h], sum(lat[h]),
                last[h]) for h in range(H)]
    elapsed = max(last) - min(first)
    return {"latency": lat, "summary": summary, "elapsed": elapsed,
            "hit": [[hit for _, _, hit in r] for r in recs],
            "metrics": (_metrics(sysm, recs, metrics)
                        if metrics is not None else None)}


def hosts(config: dict, job: dict, metrics: dict, tick_bits: int = 64) -> dict:
    """A replay in the shape the host lanes hand to the check: per-host
    latencies, per-host summaries plus the global span, the metrics."""
    ref = replay(config, job["addrs"], job["writes"], tick_bits=tick_bits,
                 metrics=metrics)
    return {"latency": ref["latency"],
            "summary": ref["summary"] + [(ref["elapsed"],)],
            "metrics": ref["metrics"]}


# --------------------------------------------------------------- metrics
def bucket(v: int, buckets: int) -> int:
    """Log bucket of a latency: exact below 8, then four linear
    sub-buckets per power of two."""
    v = max(v, 0)
    if v < 8:
        idx = v
    else:
        e = v.bit_length() - 1
        idx = 4 * e + ((v >> (e - 2)) & 3) - 4
    return min(idx, buckets - 1)


def _bucket_hi(idx: int) -> int:
    if idx < 8:
        return idx
    e = (idx + 4) // 4
    lo = (1 << e) + ((idx + 4) % 4) * (1 << (e - 2))
    return lo + (1 << (e - 2)) - 1


def _pct(hist: list, q: int):
    n = sum(hist)
    if n == 0:
        return None
    k = max(1, int(math.ceil(q / 100.0 * n)))
    run = 0
    for i, c in enumerate(hist):
        run += c
        if run >= k:
            return _bucket_hi(i)
    return None


def _metrics(sysm: System, recs, spec: dict) -> dict:
    """Histograms, windows, percentiles and counters, in the schema of the
    program's metrics bundle (``to_jsonable``)."""
    NB, T, W = spec["hist_buckets"], spec["window_ticks"], spec["num_windows"]
    hists, windows = [], []
    for r in recs:
        hist = [0] * NB
        win: dict = {}
        for issue, done, hit in r:
            hist[bucket(done - issue, NB)] += 1
            k = min(max(done // T, 0), W - 1)
            cell = win.setdefault(k, [0, 0, 0, 0])
            cell[0] += LINE
            cell[1] += done - issue
            cell[2] += 1
            cell[3] += int(hit)
        hists.append(hist)
        windows.append({str(k): v for k, v in sorted(win.items())})
    sparse = [{str(i): v for i, v in enumerate(h) if v} for h in hists]
    ports = {}
    if sysm.fabric is not None:
        hosts = [f"h{i}" for i in range(sysm.hosts)]
        devices = [f"d{i}" for i in range(sysm.hosts)]
        for (u, v), p in sorted(sysm.fabric.ports.items()):
            if p.packets:
                ports[f"{u}->{v}"] = {
                    "bytes": p.packets * LINE, "packets": p.packets,
                    "occupied_ticks": p.packets * p.occ,
                    "queued_ticks": p.queued, "qos_throttle_events": 0,
                    "bytes_by_host": dict(sorted(p.by_host.items()))}
    else:
        hosts = ["host0"]
        devices = [sysm.cfg["device"]["kind"]]
    return {
        "hosts": hosts, "devices": devices,
        "hist": sparse, "dev_hist": sparse, "windows": windows,
        "percentiles": [{f"p{q}": _pct(h, q) for q in (50, 95, 99)}
                        for h in hists],
        "media": [dict(m.c) for m in sysm.media],
        "flash": [dict(m.flash.stats) for m in sysm.media],
        "ports": ports, "ecmp": {},
    }
