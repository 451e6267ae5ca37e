"""Benchmark harness: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  fig3/fig4/fig5/fig6/policies/claims -- the paper's experiments (simulated)
  trace_sim                           -- simulator hot-loop throughput
  kernels                             -- Pallas kernel micro-benchmarks (interpret mode)
  roofline                            -- dry-run derived roofline terms (if results exist)
"""

from __future__ import annotations

import importlib
import sys

from repro.compile_cache import use_persistent_cache

MODULES = [
    "benchmarks.paper_figures",
    "benchmarks.trace_sim_speed",
    "benchmarks.replay_bench",       # also writes results/BENCH_replay.json
    "benchmarks.fabric_sweep",
    "benchmarks.kernel_bench",
    "benchmarks.ablations",
    "benchmarks.roofline_report",
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    use_persistent_cache()
    print("name,us_per_call,derived")
    for modname in MODULES:
        mod = importlib.import_module(modname)
        for fn in getattr(mod, "ALL", []):
            if only and only not in fn.__name__:
                continue
            try:
                for name, us_per_call, derived in fn():
                    print(f"{name},{us_per_call:.2f},{derived}")
            except Exception as e:
                print(f"# {fn.__name__} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
                raise


if __name__ == "__main__":
    main()
