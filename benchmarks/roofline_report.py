"""Roofline summary rows for the benchmark harness (reads dry-run JSONs)."""

from __future__ import annotations

from pathlib import Path
from typing import List, Tuple

Row = Tuple[str, float, str]


def bench_roofline_summary() -> List[Row]:
    from repro.launch.roofline import load_all

    rows: List[Row] = []
    for mesh in ("single", "multi"):
        for r in load_all("results/dryrun", mesh):
            rows.append((
                f"roofline/{mesh}/{r['arch']}/{r['shape']}",
                0.0,
                f"dom={r['dominant']};roof={100*r['roofline_fraction']:.1f}%;"
                f"compute={r['compute_s']:.4f}s;mem={r['memory_s']:.4f}s;"
                f"coll={r['collective_s']:.4f}s;"
                f"useful={100*min(r['useful_flops_ratio'],9.99):.0f}%",
            ))
    return rows


ALL = [bench_roofline_summary]
