"""The trace reduction, on a small trace recorded on a TPU v5e
(``bench/tests/data/scan1024.xplane.pb.gz``): one ``tableI.zipf`` job cut
to 1,024 accesses inside a ``bench.job`` span, with the op lines kept for
the first 8 ms of the replay module so that the file stays small."""

import gzip
from pathlib import Path

import pytest

from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "scan1024.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return tr.reduce(path, [0])


def test_window_and_lines_are_found(reduced):
    assert reduced is not None
    assert 0 < reduced.window_s < 1
    assert len(reduced.modules) == 1 and len(reduced.ops) == 1
    assert any("_run_stack" in n for _, _, n in reduced.modules[0])


def test_busy_nests_inside_module_inside_window(reduced):
    step = reduced.module_time_s("_run_stack")
    assert 0 < reduced.busy_s < reduced.module_s <= reduced.window_s
    assert 0 < step <= reduced.module_s


def test_loop_ops_are_not_leaves(reduced):
    names = {n for _, _, n in reduced.ops[0]}
    assert not any(n.startswith("%while") for n in names)
    assert all(" = " not in n for n in names)


def test_breakdown_shape(reduced):
    b = reduced.breakdown()
    assert 0 < len(b["device_ops"]) <= tr.TOP
    assert 0 < len(b["idle_gaps"]) <= tr.TOP
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(v for _, v in b["idle_gaps"]) <= reduced.window_s


def test_union_and_gaps():
    iv = [(0, 4, "a"), (2, 6, "b"), (8, 9, "c"), (20, 30, "d")]
    assert tr.union(iv, 1, 25) == 5 + 1 + 5
    assert tr.gaps(iv, 0, 25) == [(6, 8), (9, 20)]
    assert tr.leaves([(0, 10, "%while.1 = x"), (1, 2, "%f.1 = y"),
                      (3, 4, "%f.2 = z")]) == [(1, 2, "%f.1"), (3, 4, "%f.2")]
