"""The benchmark's copy of the trace generator is bit-equal to the
program's (``repro.data.workloads.host_trace_np``) at every cell's
parameters, on small and large seeds and several host indices."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import gen

BENCH = Path(__file__).resolve().parents[1]
TRAFFIC = sorted(BENCH.glob("traffic/*.json")) + sorted(
    BENCH.glob("tests/data/traffic-*.json"))


@pytest.mark.parametrize("path", TRAFFIC, ids=[p.stem for p in TRAFFIC])
@pytest.mark.parametrize("seed", [0, 1234567891, 2**31 + 7, 2**40 + 3])
def test_generator_matches_program(path, seed):
    from repro.data import WorkloadSpec, host_trace_np

    t = json.loads(path.read_text())
    spec = WorkloadSpec(**t["generator"])
    n = min(t["accesses"], 4096)
    for host in (0, 5, 8 * t["hosts"] - 1):
        a, w = gen.host_trace(t["generator"], seed, host, n)
        ra, rw = host_trace_np(spec, seed, host, n)
        assert a.dtype == ra.dtype and np.array_equal(a, ra)
        assert np.array_equal(w, rw)


@pytest.mark.parametrize("kind,extra", [
    ("hotspot", {"hot_frac": 0.8, "hot_pages": 100}),
    ("bursty", {"on_len": 32, "off_len": 96}),
    ("scan", {"stride_pages": 3}),
    ("zipfian", {"zipf_s": 1.2})])
def test_other_kinds_match_program(kind, extra):
    from repro.data import WorkloadSpec, host_trace_np

    g = {"kind": kind, "num_pages": 4096, "write_frac": 0.25, **extra}
    a, w = gen.host_trace(g, 99, 3, 2048)
    ra, rw = host_trace_np(WorkloadSpec(**g), 99, 3, 2048)
    assert np.array_equal(a, ra) and np.array_equal(w, rw)
