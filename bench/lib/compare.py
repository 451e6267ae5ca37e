"""Exact comparison of a lane's outputs with the plain reference's.

Every number is a count of mismatches, so each limit is 0: the fused lanes
are tick-exact by contract, and any difference is a wrong answer.
"""

from __future__ import annotations

import numpy as np


def latency(got, want) -> int:
    """Accesses whose latency differs (a missing or extra access counts)."""
    bad = 0
    for g, w in zip(got, want):
        g = np.asarray(g, np.int64)
        w = np.asarray(w, np.int64)
        m = min(g.size, w.size)
        bad += int((g[:m] != w[:m]).sum()) + abs(g.size - w.size)
    return bad + sum(np.asarray(x).size for x in got[len(want):]) \
        + sum(np.asarray(x).size for x in want[len(got):])


def fields(got, want) -> int:
    """Scalar fields that differ between two flat sequences of tuples."""
    bad = abs(len(got) - len(want))
    for g, w in zip(got, want):
        g, w = tuple(int(x) for x in g), tuple(int(x) for x in w)
        bad += sum(a != b for a, b in zip(g, w)) + abs(len(g) - len(w))
    return bad


def bundle(got: dict, want: dict) -> int:
    """Top-level entries of the reference's metrics dict that the program's
    bundle does not reproduce exactly."""
    return sum(got.get(k) != v for k, v in want.items())


def hosts(out: dict, ref: dict) -> dict:
    """Per-host latencies, per-host and global summaries, and the metrics
    bundle of a replay against the reference's."""
    return {"latency": latency(out["latency"], ref["latency"]),
            "summary": fields(out["summary"], ref["summary"]),
            "bundle": bundle(out["metrics"], ref["metrics"])}


def same(a: dict, b: dict) -> bool:
    """Two outputs of one lane for one job are identical."""
    for key, va in a.items():
        vb = b.get(key)
        if key in ("latency", "hit"):
            if len(va) != len(vb) or latency(va, vb):
                return False
        elif key == "summary":
            if fields(va, vb):
                return False
        elif va != vb:
            return False
    return True
