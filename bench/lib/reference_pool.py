"""Plain reference for a pooled deployment: hosts in logical devices of one
cached CXL-SSD behind one switch, one access at a time.

Written for the benchmark and importing nothing of the program.  The
device (DRAM page cache, flash, NAND) and the switch port are
``reference.py``'s classes, taken by import; what this file adds is the
pool (a configuration with ``attach: pool``):

* one CXL 2.0 switch ``s0``: host ``h<i>`` reaches device ``d0`` over the
  ports ``h<i>->s0`` and ``s0->d0``, each serializing the 64 B line on its
  own busy-until; ``s0`` adds its store-and-forward latency, and the
  round-trip extra is charged once per access;
* one cached CXL-SSD shared by every host, partitioned into logical
  devices (LDs) of ``ld_bytes``: host ``i`` owns LD ``i``, so its address
  ``a`` is the device address ``i * ld_bytes + a``; an access outside
  ``[0, ld_bytes)`` is an error;
* the hosts interleave in global issue-time order, ties to the lower host
  index, each through ``outstanding`` line-fill-buffer slots, as
  ``reference.replay`` issues them.

:func:`hosts` returns what ``reference.hosts`` returns, with a metrics dict
for one device ``d0`` and the LD table.
"""

from __future__ import annotations

import heapq

from bench.lib.reference import (LINE, CachedSSD, Flash, Port, _ident,
                                 _pct, _wrap32, bucket, ns)


class SingleSwitch:
    """``single_switch`` with every host and one device on switch ``s0``."""

    def __init__(self, fab: dict, hosts: int, w):
        self.w = w
        self.fwd = ns(fab["forward_ns"])
        self.rt = ns(fab["rt_extra_ns"])
        self.ports = {(f"h{i}", "s0"): Port(fab["bw_gbps"])
                      for i in range(hosts)}
        self.ports[("s0", "d0")] = Port(fab["bw_gbps"])

    def _hop(self, key, t: int, host: int) -> int:
        p = self.ports[key]
        start = max(t, p.busy)
        p.queued += start - t
        p.busy = self.w(start + p.occ)
        p.packets += 1
        p.by_host[f"h{host}"] = p.by_host.get(f"h{host}", 0) + LINE
        return p.busy

    def traverse(self, now: int, host: int) -> int:
        t = self.w(self._hop((f"h{host}", "s0"), now, host) + self.fwd)
        t = self._hop(("s0", "d0"), t, host)
        return self.w(t + self.rt)


class Pool:
    """The deployment: hosts, the switch and the one partitioned device."""

    def __init__(self, config: dict, tick_bits: int = 64):
        w = _wrap32 if tick_bits == 32 else _ident
        self.w = w
        fab, dev = config["fabric"], config["device"]
        if config["attach"] != "pool" or fab["kind"] != "single_switch" \
                or fab["devices"] != 1:
            raise ValueError("modelled here: a pool of one device on "
                             "single_switch")
        if dev["kind"] != "cxl-ssd-cache":
            raise ValueError(f"device {dev['kind']!r} not modelled here")
        if fab.get("qos"):
            raise ValueError("switch QoS is not modelled here")
        self.hosts = config["hosts"]
        self.ld_bytes = fab["ld_bytes"]
        if self.hosts * self.ld_bytes > dev["ssd"]["capacity_bytes"]:
            raise ValueError("the LDs do not fit the device")
        self.media = CachedSSD(dev["cache"], Flash(dev["ssd"], w), w)
        self.fabric = SingleSwitch(fab, self.hosts, w)

    def service(self, host: int, now: int, addr: int, write: bool,
                posted: bool):
        if not 0 <= addr <= self.ld_bytes - LINE:
            raise ValueError(f"host {host}: address {addr:#x} outside its "
                             f"LD of {self.ld_bytes:#x} bytes")
        t = self.fabric.traverse(now, host)
        return self.media.access(t, host * self.ld_bytes + addr, write,
                                 posted)


def replay(config: dict, addrs, writes, tick_bits: int = 64,
           metrics: dict | None = None):
    """Replay ``addrs``/``writes`` (one row per host) on a fresh pool; the
    return value is ``reference.replay``'s."""
    pool = Pool(config, tick_bits)
    w = pool.w
    H = pool.hosts
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
        done, hit = pool.service(h, issue, a, wr, wr and posted_writes)
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
    return {"latency": lat, "summary": summary,
            "elapsed": max(last) - min(first),
            "hit": [[hit for _, _, hit in r] for r in recs],
            "metrics": (_metrics(pool, recs, metrics)
                        if metrics is not None else None)}


def hosts(config: dict, job: dict, metrics: dict, tick_bits: int = 64) -> dict:
    """A replay in the shape the host lanes hand to the check."""
    ref = replay(config, job["addrs"], job["writes"], tick_bits=tick_bits,
                 metrics=metrics)
    return {"latency": ref["latency"],
            "summary": ref["summary"] + [(ref["elapsed"],)],
            "metrics": ref["metrics"]}


def _metrics(pool: Pool, recs, spec: dict) -> dict:
    """``reference._metrics``'s schema for one device ``d0`` shared by
    every host (its histogram holds every access), plus the LD table."""
    NB, T, W = spec["hist_buckets"], spec["window_ticks"], spec["num_windows"]
    hists, windows = [], []
    dev_hist = [0] * NB
    for r in recs:
        hist = [0] * NB
        win: dict = {}
        for issue, done, hit in r:
            b = bucket(done - issue, NB)
            hist[b] += 1
            dev_hist[b] += 1
            k = min(max(done // T, 0), W - 1)
            cell = win.setdefault(k, [0, 0, 0, 0])
            cell[0] += LINE
            cell[1] += done - issue
            cell[2] += 1
            cell[3] += int(hit)
        hists.append(hist)
        windows.append({str(k): v for k, v in sorted(win.items())})
    ports = {}
    for (u, v), p in sorted(pool.fabric.ports.items()):
        if p.packets:
            ports[f"{u}->{v}"] = {
                "bytes": p.packets * LINE, "packets": p.packets,
                "occupied_ticks": p.packets * p.occ,
                "queued_ticks": p.queued, "qos_throttle_events": 0,
                "bytes_by_host": dict(sorted(p.by_host.items()))}
    return {
        "hosts": [f"h{i}" for i in range(pool.hosts)], "devices": ["d0"],
        "hist": [{str(i): v for i, v in enumerate(h) if v} for h in hists],
        "dev_hist": [{str(i): v for i, v in enumerate(dev_hist) if v}],
        "windows": windows,
        "percentiles": [{f"p{q}": _pct(h, q) for q in (50, 95, 99)}
                        for h in hists],
        "media": [dict(pool.media.c)],
        "flash": [dict(pool.media.flash.stats)],
        "ports": ports, "ecmp": {},
        "lds": [{"ld": i, "base": i * pool.ld_bytes, "bytes": pool.ld_bytes}
                for i in range(pool.hosts)],
    }
