"""Shared test sizes: every configuration-traffic pair the harness has a
lane for, cut to what a CPU test run can hold."""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]

# (configuration file, traffic file) of each pair: the cells of
# BENCHMARK.json, and a test fixture that keeps the multihost lane, which
# no cell uses yet, rehearsed
PAIRS = {
    "tableI.zipf": ("configs/tableI-1host.json", "traffic/zipf.json"),
    "tableI.sweep64": ("configs/tableI-1host.json", "traffic/sweep64.json"),
    "fixture.4host": ("tests/data/config-4host-two_level.json",
                      "tests/data/traffic-zipf-4host.json"),
}

SMALL = {
    "tableI.zipf": {"accesses": 2048},
    "fixture.4host": {"accesses": 512},
    "tableI.sweep64": {"accesses": 512, "checked_points_per_job": 2,
                       "design_points": {
                           "capacity_frames": [128, 1024, 4096, 4096],
                           "policy": ["lru", "fifo", "lru", "fifo"]}},
}


def cpu_run(cell: str, seed: int = 2**31 + 11, **kw) -> dict:
    """One harness run of ``cell`` on the CPU at its test size, past the
    look for a chip."""
    import jax

    from bench import run

    return run.run_cell(cell, seed, 0.5, False, jax.devices(), cache=False,
                        traffic_override=SMALL[cell], **kw)


def lane_of(pair: str, override: dict | None = None):
    """``(lane module, ctx, traffic)`` of a pair, straight from its files."""
    from bench import run

    conf, mix = PAIRS[pair]
    config = json.loads((ROOT / "bench" / conf).read_text())
    traffic = {**json.loads((ROOT / "bench" / mix).read_text()),
               **(override or {})}
    lane = run.load_module("lanes", traffic.get("lane", config["lane"]))
    return lane, lane.setup(config, traffic), traffic
