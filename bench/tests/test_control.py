"""The control: the plain reference in the program's place, with every
tick stored as a signed 32-bit integer (the precision below the
configurations' int64 picosecond ticks), has to come out not correct.

As a test it runs each cell's lane comparison at a test size.  As a
script it reads the control's numbers at a cell's own size over the pool
entries a run compares, on several seeds:

    python3 bench/tests/test_control.py --workload tableI.zipf --seeds 1 2 3
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import run  # noqa: E402
from bench.tests.cells import PAIRS, lane_of  # noqa: E402

# the control needs enough simulated time to pass 2^31 ps (2.1 ms)
CONTROL_SIZE = {"tableI.zipf": {"accesses": 32768},
                "fixture.4host": {"accesses": 16384},
                "tableI.sweep64": {"accesses": 8192}}


def control_readings(cell: str, seed: int, entries: int = run.POOL,
                     override: dict | None = None) -> dict:
    """Summed mismatch counts of the control against the reference over
    the first ``entries`` pool entries of ``seed``."""
    lane, ctx, traffic = lane_of(cell, override)
    pool = run.make_pool(traffic, seed)
    totals: dict = {}
    for k in range(entries):
        ref = lane.reference_out(ctx, pool[k], np.random.default_rng([seed, k]))
        ctl = lane.reference_out(ctx, pool[k], np.random.default_rng([seed, k]),
                                 tick_bits=32)
        for name, v in lane.check(ctx, ctl, ref).items():
            totals[name] = totals.get(name, 0) + v
    return totals


@pytest.mark.parametrize("cell", sorted(PAIRS))
def test_control_is_not_correct(cell):
    got = control_readings(cell, 2**31 + 5, entries=2,
                           override=CONTROL_SIZE[cell])
    assert got["latency"] > 0, got


@pytest.mark.parametrize("cell", sorted(PAIRS))
def test_reference_agrees_with_itself(cell):
    """The comparison reads 0 when nothing differs (the control's lower
    reading), so a nonzero control reading is the precision's doing."""
    lane, ctx, traffic = lane_of(cell, CONTROL_SIZE[cell])
    job = run.make_pool(traffic, 3)[0]
    a = lane.reference_out(ctx, job, np.random.default_rng(0))
    b = lane.reference_out(ctx, job, np.random.default_rng(0))
    assert all(v == 0 for v in lane.check(ctx, a, b).values())


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    for s in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": s,
                          "control": control_readings(args.workload, s)}),
              flush=True)
