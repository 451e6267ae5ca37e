"""JAX-native seeded workload generators (repro.data.workloads): the
scalar / numpy / jnp twins must be bit-equal per element, deterministic per
(seed, shape), and the distributions must actually have the shape their
names promise (Zipf rank-frequency slope, hotspot mass concentration,
bursty duty cycle, scan periodicity)."""

import numpy as np
import pytest
from jax import enable_x64

from repro.data import (
    WORKLOAD_KINDS,
    WorkloadSpec,
    access_at,
    host_trace_jnp,
    host_trace_np,
    make_traces,
    traces_np,
    zipf_cdf,
)

SPECS = {
    "zipfian": WorkloadSpec("zipfian", num_pages=512, zipf_s=1.1),
    "hotspot": WorkloadSpec("hotspot", num_pages=256, hot_frac=0.85,
                            hot_pages=16),
    "bursty": WorkloadSpec("bursty", num_pages=384, on_len=32, off_len=96),
    "scan": WorkloadSpec("scan", num_pages=200, stride_pages=3),
}


@pytest.mark.parametrize("kind", WORKLOAD_KINDS)
def test_deterministic_per_seed_and_shape(kind):
    spec = SPECS[kind]
    a1, w1 = host_trace_np(spec, 7, 3, 400)
    a2, w2 = host_trace_np(spec, 7, 3, 400)
    assert np.array_equal(a1, a2) and np.array_equal(w1, w2)
    # a longer trace is a prefix-extension, not a reshuffle
    a3, _ = host_trace_np(spec, 7, 3, 800)
    assert np.array_equal(a3[:400], a1)
    # seed and host both move the stream (scan's pages are index-only,
    # but its line offsets and writes still draw from the hash)
    ds, _ = host_trace_np(spec, 8, 3, 400)
    dh, _ = host_trace_np(spec, 7, 4, 400)
    assert not np.array_equal(ds, a1)
    assert not np.array_equal(dh, a1)


@pytest.mark.parametrize("kind", WORKLOAD_KINDS)
def test_scalar_numpy_jnp_twins_bit_equal(kind):
    spec = SPECS[kind]
    n = 300
    an, wn = host_trace_np(spec, 11, 2, n)
    for i in range(0, n, 37):
        a, w = access_at(spec, 11, 2, i)
        assert (a, w) == (int(an[i]), bool(wn[i]))
    with enable_x64(True):
        aj, wj = host_trace_jnp(spec, 11, 2, n)
        assert np.array_equal(np.asarray(aj), an)
        assert np.array_equal(np.asarray(wj), wn)


def test_traces_np_and_make_traces_agree():
    spec = SPECS["hotspot"]
    addrs, writes = traces_np(spec, 5, 3, 64)
    assert addrs.shape == (3, 64) and writes.shape == (3, 64)
    tup = make_traces(spec, 5, 3, 64)
    assert len(tup) == 3
    for h in range(3):
        assert [a for a, _, _ in tup[h]] == list(addrs[h])
        assert [w for _, _, w in tup[h]] == list(writes[h])
        assert all(s == 64 for _, s, _ in tup[h])


def test_addresses_stay_inside_the_footprint():
    for kind, spec in SPECS.items():
        addrs, _ = host_trace_np(spec, 3, 0, 2000)
        assert addrs.min() >= 0
        assert addrs.max() < spec.num_pages * spec.page_bytes
        assert (addrs % 64 == 0).all()


def test_write_fraction_tracks_the_coin():
    spec = WorkloadSpec("scan", num_pages=64, write_frac=0.25)
    _, writes = host_trace_np(spec, 9, 0, 20_000)
    assert abs(writes.mean() - 0.25) < 0.02


def test_zipf_rank_frequency_slope():
    """log(freq) vs log(rank) of a Zipf(s) sample must have slope ~ -s."""
    spec = SPECS["zipfian"]
    addrs, _ = host_trace_np(spec, 13, 0, 60_000)
    pages = addrs // spec.page_bytes
    counts = np.bincount(pages, minlength=spec.num_pages)
    top = np.sort(counts)[::-1][:64].astype(float)
    assert (top > 0).all()
    slope = np.polyfit(np.log(np.arange(1, 65)), np.log(top), 1)[0]
    assert -1.35 < slope < -0.85       # s = 1.1
    # page 0 is the hottest rank
    assert counts.argmax() == 0
    cdf = zipf_cdf(spec.num_pages, spec.zipf_s)
    assert cdf[-1] == 1.0 and (np.diff(cdf) > 0).all()


def test_hotspot_mass_concentration():
    spec = SPECS["hotspot"]
    addrs, _ = host_trace_np(spec, 17, 1, 40_000)
    pages = addrs // spec.page_bytes
    hot = (pages < spec.hot_set_pages).mean()
    assert abs(hot - spec.hot_frac) < 0.02
    # the hot set is 16/256 of the footprint but carries ~85% of the mass
    assert hot > 4 * (spec.hot_set_pages / spec.num_pages)


def test_bursty_duty_cycle():
    spec = SPECS["bursty"]
    n = 8 * (spec.on_len + spec.off_len)
    addrs, _ = host_trace_np(spec, 19, 0, n)
    pages = addrs // spec.page_bytes
    idx = np.arange(n)
    on = idx % (spec.on_len + spec.off_len) < spec.on_len
    # ON windows hit the hot set, OFF windows stride the cold footprint
    assert (pages[on] < spec.hot_set_pages).all()
    assert np.array_equal(
        pages[~on], (idx[~on] * spec.cold_stride) % spec.num_pages)
    assert abs(on.mean() - spec.on_len / (spec.on_len + spec.off_len)) < 1e-9


def test_scan_periodicity():
    spec = SPECS["scan"]
    addrs, _ = host_trace_np(spec, 23, 0, 1000)
    pages = addrs // spec.page_bytes
    assert np.array_equal(pages,
                          (np.arange(1000) * spec.stride_pages)
                          % spec.num_pages)


def test_spec_validation():
    with pytest.raises(ValueError):
        WorkloadSpec("nope", num_pages=8)
    with pytest.raises(ValueError):
        WorkloadSpec("zipfian", num_pages=1)
    with pytest.raises(ValueError):
        WorkloadSpec("hotspot", num_pages=8, hot_pages=8)
    with pytest.raises(ValueError):
        WorkloadSpec("bursty", num_pages=8, on_len=0)
    with pytest.raises(ValueError):
        WorkloadSpec("scan", num_pages=8, stride_pages=0)
