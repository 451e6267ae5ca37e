"""Where a replay step's chip time goes: the runner's module time split by
the program's named step scopes.

The program names the parts of its step with ``jax.named_scope``
(``repro.obs.scopes.STEP_SCOPES``) and, inside ``scopes.recording()``,
keeps the optimized HLO of the runner it compiled; ``scopes.op_scopes``
maps that program's instructions to the parts.  Here a profiler trace of
one job is charged part by part: every instant of the runner's module
goes to exactly one scope (:func:`charge`), so the parts add up to the
module time that ``bench/metrics/step_us.py`` reads.
"""

from __future__ import annotations

import bisect
import time

from bench.lib import trace as tr

LOOP = "loop"          # the scan's own work, unscoped ops, the module's tail
UNNAMED = "unnamed"    # ops the program's HLO gives no op_name
# host spans that name the window's time with no module running
HOST_PREFIXES = ("replay.", "metrics.", "bench.")


def load_copies(path, device_ids) -> list:
    """Per chip, the asynchronous copies of the trace's ``Async XLA Ops``
    line: ``(start, end, name)`` from ``copy-start`` to ``copy-done``,
    the DMA in flight."""
    from jax.profiler import ProfileData

    want = {f"{tr.DEVICE_PLANE}{i}" for i in device_ids}
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name in want:
            for line in plane.lines:
                if line.name == "Async XLA Ops":
                    out.append(tr._events(line))
    return out


def charge(ops, lo: float, hi: float, scope_of) -> dict:
    """Charge every instant of ``[lo, hi]`` to one scope: a leaf op's run
    to ``scope_of(name)``, the idle time before it to the same scope (the
    op the core waits to launch), and the time after the last op to
    :data:`LOOP`.  ``ops`` are one chip's leaf ops ``(start, end, name)``
    sorted by start.  Returns the ``charged`` and ``busy`` time per scope
    (``charged`` sums to ``hi - lo``), the idle ``gaps`` and the count of
    ``ops`` that started in the interval."""
    charged: dict = {}
    busy: dict = {}
    idle = []
    t, count = lo, 0
    i = bisect.bisect_left(ops, (lo,))
    while i < len(ops) and ops[i][0] < hi:
        s, e, name = ops[i]
        i += 1
        sc = scope_of(name)
        count += 1
        if s > t:
            charged[sc] = charged.get(sc, 0.0) + (s - t)
            idle.append((t, s))
            t = s
        e = min(e, hi)
        if e > t:
            charged[sc] = charged.get(sc, 0.0) + (e - t)
            busy[sc] = busy.get(sc, 0.0) + (e - t)
            t = e
    if t < hi:
        charged[LOOP] = charged.get(LOOP, 0.0) + (hi - t)
        idle.append((t, hi))
    return {"charged": charged, "busy": busy, "gaps": idle, "ops": count}


def overlaps(a, b) -> list:
    """The intersections of the disjoint intervals ``a`` (sorted) with the
    intervals ``b``."""
    b = sorted((s, e) for s, e, *_ in b)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def step_parts(reduced, copies, fragment: str, scope_map) -> dict:
    """The chip time of the modules whose name holds ``fragment``, clipped
    to the window and averaged over the chips as ``Reduced.module_time_s``
    takes it, split by scope.  ``scope_map`` is ``{instruction: scope}``
    (an op it does not name is :data:`UNNAMED`).  Returns seconds:
    ``charged`` and ``busy`` per scope, ``module``, ``dma_wait`` (no op
    running and a copy in flight), and the count of leaf ops, ``ops``."""
    smap = scope_map or {}

    def scope_of(name):
        return smap.get(name.lstrip("%"), UNNAMED)

    k = max(1, len(reduced.modules))
    tot = {"charged": {}, "busy": {}, "module": 0.0, "dma_wait": 0.0,
           "ops": 0.0}
    for c, chip in enumerate(reduced.modules):
        ops = reduced.ops[c] if c < len(reduced.ops) else []
        cps = copies[c] if c < len(copies) else []
        for s, e, name in chip:
            if fragment not in name or e <= reduced.lo or s >= reduced.hi:
                continue
            a, b = max(s, reduced.lo), min(e, reduced.hi)
            part = charge(ops, a, b, scope_of)
            for kind in ("charged", "busy"):
                for sc, v in part[kind].items():
                    tot[kind][sc] = tot[kind].get(sc, 0.0) + v / k / 1e9
            tot["module"] += (b - a) / k / 1e9
            tot["dma_wait"] += tr.union(overlaps(part["gaps"], cps),
                                        a, b) / k / 1e9
            tot["ops"] += part["ops"] / k
    return tot


def idle_by_span(reduced) -> dict:
    """Seconds of the window with no module running on the first chip, by
    the innermost host span named ``replay.*``, ``metrics.*`` or
    ``bench.*`` around each instant."""
    named = [x for x in reduced.spans if x[2].startswith(HOST_PREFIXES)]
    out: dict = {}
    for gs, ge in tr.gaps(reduced.modules[0] if reduced.modules else [],
                          reduced.lo, reduced.hi):
        cuts = sorted({gs, ge, *(t for s, e, _ in named for t in (s, e)
                                 if gs < t < ge)})
        for s, e in zip(cuts, cuts[1:]):
            m = (s + e) / 2
            inner = [x for x in named if x[0] <= m <= x[1]]
            name = (min(inner, key=lambda x: x[1] - x[0])[2] if inner
                    else "(no span)")
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def scope_maps(lane, ctx, job) -> tuple:
    """Replay ``job`` with the program's scope recording on; returns the
    inferred and the strict ``{instruction: scope}`` maps of the lane's
    runner (``None, None`` for a program without named scopes) and the
    job's seconds."""
    try:
        from repro.obs import scopes
    except ImportError:
        return None, None, 0.0
    t0 = time.perf_counter()
    with scopes.recording() as kept:
        lane.run(ctx, job)
    secs = time.perf_counter() - t0
    text = kept.get(lane.MODULE)
    if text is None:
        return None, None, secs
    return scopes.op_scopes(text), scopes.op_scopes(text, infer=False), secs
