"""The split of a step by named scope (``bench/lib/step_parts.py``, and
by hand ``bench/step_split.py``) and the count of ops per step
(``bench/metrics/ops_per_step.py``).

On synthetic intervals, and on the recorded TPU v5e trace of
``test_trace.py`` (``data/scan1024.xplane.pb.gz``: one ``tableI.zipf`` job
cut to 1,024 accesses, op lines kept for the first 8 ms of the runner).
``data/scan1024.scopes.json`` maps that trace's ops to step scopes: the
instructions of ``_run_stack`` compiled for a TPU v5e at the same shapes
by the program with named scopes, whose optimized HLO without metadata is
the one that ran."""

import gzip
import json
from pathlib import Path

import pytest

from bench import run, step_split
from bench.lib import step_parts as sp
from bench.lib import trace as tr
from bench.tests.cells import SMALL

HERE = Path(__file__).resolve().parent
STEPS = 1024
PARTS = ("lfb", "transport", "media", "flash", "telemetry", "loop")
# what the three trace-read metrics read on this trace; the program's new
# host spans and scopes must leave them as they are
PINNED = {"host_gap_share": 19.316799427819088,
          "step_us": 81.3959228515625,
          "device_idle_share": 97.9330457790195}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (HERE / "data" / "scan1024.xplane.pb.gz").read_bytes()))
    return tr.reduce(path, [0]), sp.load_copies(path, [0])


@pytest.fixture(scope="module")
def scopes():
    return json.loads((HERE / "data" / "scan1024.scopes.json").read_text())


@pytest.mark.parametrize("name", sorted(PINNED))
def test_fixture_metrics_read_as_pinned(recorded, name):
    ctx = {"trace": recorded[0], "steps": STEPS, "compile_s": 0.0,
           "module": "_run_stack"}
    assert run.load_module("metrics", name).read(ctx) == PINNED[name]


def test_module_nests_in_host_spans(recorded):
    """The runner's module lies inside the lane's span inside the job's:
    host spans and device events share one clock."""
    reduced = recorded[0]
    (ms, me, _), = [m for m in reduced.modules[0] if "_run_stack" in m[2]]
    (ls, le, _), = [x for x in reduced.spans if x[2] == "scan.run_arrays"]
    (js, je, _), = [x for x in reduced.spans if x[2] == tr.JOB_SPAN]
    assert js < ls < ms < me < le < je


def test_fixture_parts_add_up_to_the_module(recorded, scopes):
    reduced, copies = recorded
    p = sp.step_parts(reduced, copies, "_run_stack", scopes)
    assert p["module"] == reduced.module_time_s("_run_stack")
    assert sum(p["charged"].values()) == pytest.approx(p["module"],
                                                       rel=1e-12)
    assert set(p["charged"]) <= set(PARTS)
    assert min(p["charged"][k] for k in ("lfb", "media", "flash")) > 0
    idle = p["module"] - sum(p["busy"].values())
    assert 0 < p["dma_wait"] < idle
    (ms, me, _), = [m for m in reduced.modules[0] if "_run_stack" in m[2]]
    assert p["ops"] == sum(1 for s, _, _ in reduced.ops[0] if ms <= s < me)


def test_ops_per_step_counts_the_split_s_ops(recorded):
    """The metric counts the ops the split charges: those that start
    inside the runner's module."""
    reduced, copies = recorded
    ctx = {"trace": reduced, "steps": STEPS, "compile_s": 0.0,
           "module": "_run_stack"}
    got = run.load_module("metrics", "ops_per_step").read(ctx)
    want = sp.step_parts(reduced, copies, "_run_stack", None)["ops"]
    assert got == want / STEPS > 0


@pytest.mark.parametrize("case,want", [
    ("clipped", 2 / 4), ("other module", None), ("dropped", None),
    ("no ops", None)])
def test_ops_per_step_on_synthetic_intervals(case, want):
    ops = [(5, 6, "%a"), (12, 13, "%b"), (20, 21, "%c"), (33, 34, "%d"),
           (45, 46, "%e")]
    r = tr.Reduced(lo=10, hi=40, ops=[[] if case == "no ops" else ops],
                   modules=[[(0, 25, "jit__run_stack(1)"),
                             (30, 50, "jit__other(2)")]],
                   dropped=case == "dropped")
    module = "_other_runner" if case == "other module" else "_run_stack"
    ctx = {"trace": r, "steps": 4, "compile_s": 0.0, "module": module}
    # only %b and %c start inside the module's part of the window
    assert run.load_module("metrics", "ops_per_step").read(ctx) == want


def test_without_a_map_every_op_is_unnamed(recorded):
    p = sp.step_parts(*recorded, "_run_stack", None)
    assert set(p["charged"]) == {sp.UNNAMED, sp.LOOP}
    assert set(p["busy"]) == {sp.UNNAMED}


def test_charge_sums_to_the_interval():
    ops = [(2, 3, "%a"), (5, 8, "%b"), (8, 9, "%a"), (12, 13, "%c")]
    scope = {"%a": "media", "%b": "flash", "%c": "lfb"}.get
    got = sp.charge(ops, 0, 20, scope)
    assert sum(got["charged"].values()) == 20
    # idle time goes to the next op to start, the tail to loop
    assert got["charged"] == {"media": 2 + 1 + 1, "flash": 2 + 3,
                              "lfb": 3 + 1, sp.LOOP: 7}
    assert got["busy"] == {"media": 2, "flash": 3, "lfb": 1}
    assert got["gaps"] == [(0, 2), (3, 5), (9, 12), (13, 20)]
    assert got["ops"] == 4


def test_charge_clips_to_the_interval():
    ops = [(0, 4, "%a"), (6, 12, "%b"), (15, 16, "%c")]
    got = sp.charge(ops, 1, 10, lambda n: n)
    # an op that started before the interval is not in it; one running
    # past its end is cut there
    assert got["charged"] == {"%b": 9} and got["ops"] == 1


def test_dma_wait_counts_only_idle_time_with_a_copy_in_flight():
    r = tr.Reduced(lo=0, hi=100, modules=[[(10, 60, "jit__run_stack(1)")]],
                   ops=[[(10, 20, "%a"), (30, 40, "%b")]])
    copies = [[(15, 35, "%copy-start.1"), (50, 70, "%copy-start.2")]]
    p = sp.step_parts(r, copies, "_run_stack", {"a": "media", "b": "flash"})
    # in flight over 15-35 and 50-70; no op runs over 20-30 and 40-60
    assert p["dma_wait"] == pytest.approx(20e-9)
    assert p["charged"] == pytest.approx(
        {"media": 10e-9, "flash": 20e-9, sp.LOOP: 20e-9})
    assert p["module"] == pytest.approx(50e-9) and p["ops"] == 2


@pytest.mark.parametrize("cell", sorted(SMALL.keys() - {"fixture.4host"}))
def test_measure_rehearses_on_cpu(cell):
    """The by-hand run at a test size: the recorded scope map is there;
    a CPU trace has no chip plane, so no split."""
    import jax

    res = step_split.measure(cell, 2**31 + 5, jax.devices(), cache=False,
                     traffic_override=SMALL[cell])
    assert res["scope_map_instructions"] > 0 and res["parts"] is None
    assert min(res["job_s"], res["traced_job_s"], res["scope_job_s"]) > 0
