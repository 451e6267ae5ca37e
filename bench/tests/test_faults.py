"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program (or in what the lane hands it) and a
whole harness run is driven at a test size on the CPU: the step returns
its state unchanged; a latency is altered where the step produces it; half
of every job's trace is left out.  (The cells run on one chip, so there is
no exchange between chips to leave out.)"""

import types

import jax
import pytest

from bench.tests.cells import CELLS, cpu_run


def _lane_of(cell):
    from bench import run

    _, _, config, traffic = run.load_cell(cell)
    return run.load_module("lanes", traffic.get("lane", config["lane"]))


@pytest.fixture
def fresh():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _patch_step(monkeypatch, fn):
    from repro.core.replay import stack

    real = stack.step

    def patched(cfg, p, st, access):
        return fn(real, cfg, p, st, access)

    monkeypatch.setattr(stack, "step", patched)


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_caught(cell, monkeypatch, fresh):
    _patch_step(monkeypatch, lambda real, cfg, p, st, a:
                (st, real(cfg, p, st, a)[1]))
    r = cpu_run(cell)
    assert not r["correct"] and r["checks"]["latency"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced_is_caught(cell, monkeypatch, fresh):
    def bump(real, cfg, p, st, a):
        st, out = real(cfg, p, st, a)
        return st, {**out, "done": out["done"] + (a["ctr"] == 7)}

    _patch_step(monkeypatch, bump)
    r = cpu_run(cell)
    assert not r["correct"] and r["checks"]["latency"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_is_caught(cell):
    lane = _lane_of(cell)

    def half(ctx, job):
        n = job["addrs"].shape[1] // 2
        return lane.run(ctx, {**job, "addrs": job["addrs"][:, :n],
                              "writes": job["writes"][:, :n]})

    broken = types.SimpleNamespace(**{k: getattr(lane, k)
                                      for k in dir(lane)
                                      if not k.startswith("__")})
    broken.run = half
    r = cpu_run(cell, lane=broken)
    assert not r["correct"] and r["checks"]["latency"]["value"] > 0
