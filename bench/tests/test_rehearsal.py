"""CPU rehearsal: each cell runs a whole harness run at a tiny size, and
each lane's job at a tiny size equals the plain reference's, through the
same lane code.  Run by hand: ``JAX_PLATFORMS=cpu python -m pytest
bench/tests``."""

import numpy as np
import pytest

from bench import run
from bench.tests.cells import CELLS, PAIRS, SMALL, cpu_run, lane_of


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(cell):
    r = cpu_run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["metrics"]["sim_accesses_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert all(c["limit"] == 0 for c in r["checks"].values())


@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_lane_job_equals_reference(pair):
    lane, ctx, traffic = lane_of(pair, SMALL[pair])
    job = run.make_pool(traffic, 2**33 + 1)[3]
    out = lane.run(ctx, job)
    ref = lane.reference_out(ctx, job, np.random.default_rng(3))
    assert all(v == 0 for v in lane.check(ctx, out, ref).values())
    assert lane.same(out, lane.run(ctx, job))
