"""The pool lane (``mld8.zipf``) on the CPU: its job equals the plain
pool reference, the reference at int32 ticks does not, a whole harness run
is correct, and a run with the timed path broken is not.  Run by hand:
``JAX_PLATFORMS=cpu python -m pytest bench/tests/test_pool_lane.py``."""

import types

import jax
import numpy as np
import pytest

from bench import run

CELL = "mld8.zipf"
SMALL = {"accesses": 256}        # 8 hosts x 256: 2,048 steps a job
SEED = 2**31 + 17


def _lane(override=None):
    _, _, config, traffic = run.load_cell(CELL)
    traffic = {**traffic, **(override or {})}
    lane = run.load_module("lanes", traffic.get("lane", config["lane"]))
    return lane, lane.setup(config, traffic), traffic


def _cpu_run(lane=None):
    return run.run_cell(CELL, SEED, 0.5, False, jax.devices(), cache=False,
                        lane=lane, traffic_override=SMALL)


def test_lane_job_equals_reference():
    lane, ctx, traffic = _lane(SMALL)
    job = run.make_pool(traffic, 2**33 + 1)[3]
    out = lane.run(ctx, job)
    ref = lane.reference_out(ctx, job, np.random.default_rng(3))
    assert all(v == 0 for v in lane.check(ctx, out, ref).values())
    assert lane.same(out, lane.run(ctx, job))
    lds = out["metrics"]["lds"]
    assert [d["base"] for d in lds] == [i << 31 for i in range(8)]
    by_host = out["metrics"]["ports"]["s0->d0"]["bytes_by_host"]
    assert by_host == {f"h{i}": 256 * 64 for i in range(8)}


def test_reference_at_int32_ticks_is_not_correct():
    """The control at the cell's own size: a job's simulated time passes
    2^31 ps, so int32 ticks wrap and the comparison reads mismatches."""
    lane, ctx, traffic = _lane()
    job = run.make_pool(traffic, SEED)[0]
    ref = lane.reference_out(ctx, job, None)
    ctl = lane.reference_out(ctx, job, None, tick_bits=32)
    assert all(v == 0 for v in lane.check(
        ctx, lane.reference_out(ctx, job, None), ref).values())
    assert lane.check(ctx, ctl, ref)["latency"] > 0


def test_reference_refuses_an_access_outside_its_ld():
    from bench.lib import reference_pool

    _, _, config, _ = run.load_cell(CELL)
    ld = config["fabric"]["ld_bytes"]
    addrs = np.zeros((8, 1), np.int64)
    addrs[5, 0] = ld
    with pytest.raises(ValueError, match="outside its LD"):
        reference_pool.replay(config, addrs, np.zeros((8, 1), bool))


def test_cell_runs_correct_on_cpu():
    r = _cpu_run()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["metrics"]["sim_accesses_per_s"]["value"] > 0
    assert all(c["limit"] == 0 for c in r["checks"].values())


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


def test_state_left_unchanged_is_caught(monkeypatch, fresh):
    _patch_step(monkeypatch, lambda real, cfg, p, st, a:
                (st, real(cfg, p, st, a)[1]))
    r = _cpu_run()
    assert not r["correct"] and r["checks"]["latency"]["value"] > 0


def test_answer_altered_where_produced_is_caught(monkeypatch, fresh):
    def bump(real, cfg, p, st, a):
        st, out = real(cfg, p, st, a)
        return st, {**out, "done": out["done"] + (a["ctr"] == 7)}

    _patch_step(monkeypatch, bump)
    r = _cpu_run()
    assert not r["correct"] and r["checks"]["latency"]["value"] > 0


def test_half_the_batch_left_out_is_caught():
    lane, _, _ = _lane()

    def half(ctx, job):
        n = job["addrs"].shape[1] // 2
        return lane.run(ctx, {**job, "addrs": job["addrs"][:, :n],
                              "writes": job["writes"][:, :n]})

    broken = types.SimpleNamespace(**{k: getattr(lane, k)
                                      for k in dir(lane)
                                      if not k.startswith("__")})
    broken.run = half
    r = _cpu_run(lane=broken)
    assert not r["correct"] and r["checks"]["latency"]["value"] > 0


def test_ld_bases_stack_the_hosts_in_one_device():
    """Host i's page p is device page i * ld_bytes / 4096 + p: the largest
    address of the cell sits in LD 7, so the fused map covers 2^22
    pages, the whole 16 GiB device."""
    from repro.core.replay import MultiHostReplay

    lane, ctx, traffic = _lane(SMALL)
    job = run.make_pool(traffic, SEED)[0]
    eng = MultiHostReplay(lane.views(ctx["config"]))
    cfg, _, devs, addrs, _, _, _ = eng.prepare_arrays(job["addrs"],
                                                      job["writes"])
    assert (devs == 0).all()
    np.testing.assert_array_equal(
        addrs - job["addrs"],
        np.broadcast_to((np.arange(8) << 31)[:, None], addrs.shape))
    assert cfg.stack.num_pages == 1 << 22
