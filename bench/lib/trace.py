"""Reduction of a profiler trace (``.xplane.pb``) to intervals.

Reads, with nothing but ``jax.profiler.ProfileData``:

* on each used chip's device plane (``/device:TPU:<id>``), the line of XLA
  modules (one event per program execution) and the line of XLA ops (one
  event per op execution, also inside loop bodies; the loop op itself
  encloses its body's ops, so only the leaves count);
* on the host plane (``/host:CPU``), the thread line that holds the
  benchmark's ``bench.job`` spans, with every event on it (the lanes'
  own spans and the runtime's), to name what the host was doing in a gap.

The window runs from the start of the first ``bench.job`` span to the end
of the last.  A trace whose device buffers overflowed (the runtime's "Trace Buffers
Dropped" event) is marked, and no metric is read from what it lost.  Busy time is the union of op intervals inside it, averaged
over the chips; a gap is an interval of the window in which no op (or no
module) runs.
"""

from __future__ import annotations

from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
TRACEME_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
JOB_SPAN = "bench.job"
TOP = 10


def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """``(start, end)`` stretches of ``[lo, hi]`` no interval covers."""
    out, t = [], lo
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


@dataclass
class Reduced:
    """Intervals of one traced window, in nanoseconds on one clock."""

    lo: float
    hi: float
    modules: list = field(default_factory=list)   # per chip: [(s, e, name)]
    ops: list = field(default_factory=list)       # per chip: [(s, e, name)]
    spans: list = field(default_factory=list)     # host: [(s, e, name)]
    dropped: bool = False     # the chip's trace buffers overflowed

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _mean_union(self, per_chip) -> float:
        if not per_chip:
            return 0.0
        return sum(union(c, self.lo, self.hi) for c in per_chip) \
            / len(per_chip) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return self._mean_union(self.ops)

    @property
    def module_s(self) -> float:
        """Seconds in which a module ran, averaged over the chips."""
        return self._mean_union(self.modules)

    def module_time_s(self, fragment: str) -> float:
        """Summed duration of the modules whose name holds ``fragment``,
        averaged over the chips."""
        if not self.modules:
            return 0.0
        return sum(sum(min(e, self.hi) - max(s, self.lo)
                       for s, e, n in chip if fragment in n and e > self.lo
                       and s < self.hi)
                   for chip in self.modules) / len(self.modules) / 1e9

    def span_at(self, t: float) -> str:
        """The innermost host span around ``t``."""
        best = None
        for s, e, n in self.spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "(no host span)"

    def breakdown(self) -> dict:
        """The device ops that took most time, and the longest idle gaps of
        the first chip named by the host span they fall in."""
        tot: dict = {}
        for chip in self.ops:
            for s, e, n in chip:
                if e > self.lo and s < self.hi:
                    tot[n] = tot.get(n, 0.0) + (min(e, self.hi)
                                                - max(s, self.lo)) / 1e9
        k = max(1, len(self.ops))
        ops = sorted(((n, v / k) for n, v in tot.items()),
                     key=lambda x: -x[1])[:TOP]
        base = self.ops[0] if self.ops and self.ops[0] else (
            self.modules[0] if self.modules else [])
        gl = sorted(gaps(base, self.lo, self.hi), key=lambda g: g[0] - g[1])
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[self.span_at((s + e) / 2), (e - s) / 1e9]
                              for s, e in gl[:TOP]]}


def _events(line) -> list:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def leaves(events) -> list:
    """The ops that hold no other op.  A chip runs one op at a time, so an
    op that another starts inside is a container (a ``while`` loop, a
    ``conditional``) and would cover the gaps between its body's ops.
    Names are shortened to the HLO instruction name (``%fusion.12``)."""
    ev = sorted(events, key=lambda x: (x[0], -x[1]))
    out = []
    for i, (s, e, n) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][0] < e:
            continue
        out.append((s, e, n.split(" = ", 1)[0]))
    return out


def _load(path, device_ids):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, jobs, modules, ops = [], [], [], []
    dropped = None
    want = {f"{DEVICE_PLANE}{i}" for i in device_ids}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                ev = _events(line)
                mine = [x for x in ev if x[2] == JOB_SPAN]
                if mine:
                    jobs += mine
                    spans += ev
        elif plane.name in want:
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules.append(_events(line))
                elif line.name == OP_LINE:
                    ops.append(leaves(_events(line)))
                elif line.name == TRACEME_LINE:
                    for e in line.events:
                        if e.name == DROPPED and (dropped is None
                                                  or e.start_ns < dropped):
                            dropped = e.start_ns
    return jobs, spans, modules, ops, dropped


def reduce(path, device_ids) -> Reduced | None:
    """The window of whole jobs: from the first ``bench.job`` span's start
    to the last one's end; ``None`` when the trace holds none."""
    jobs, spans, modules, ops, dropped = _load(path, device_ids)
    if not jobs:
        return None
    hi = max(e for _, e, _ in jobs)
    if dropped is not None:
        hi = min(hi, dropped)       # nothing after the overflow was kept
    return Reduced(lo=min(s for s, _, _ in jobs), hi=hi, modules=modules,
                   ops=ops, spans=spans, dropped=dropped is not None)
