"""Seeded access-trace generator: the benchmark's own copy.

A copy of the numpy twin of ``repro.data.workloads`` (``host_trace_np``,
``zipf_cdf``) and of the splitmix64 decision hash it draws from
(``repro.core.faults.plan.fault_hash_np``), kept here so that no change to
the program can move the yardstick.  Every access is a pure function of
``(params, seed, host, i)``: no RNG state, no clock.  A test under
``bench/tests`` holds it bit-equal to the program's generator.

Four kinds: ``zipfian`` (page rank from Zipf(s), page 0 hottest),
``hotspot`` (a ``hot_frac`` coin into the first ``hot_pages`` pages),
``bursty`` (ON windows on the hot set, OFF windows striding the
footprint) and ``scan`` (``(i * stride_pages) % num_pages``).
"""

from __future__ import annotations

import numpy as np

M64 = (1 << 64) - 1
M32 = (1 << 32) - 1
GOLDEN = 0x9E3779B97F4A7C15
MULT1 = 0xBF58476D1CE4E5B9
MULT2 = 0x94D049BB133111EB

# per-stream salts of the program's generator
SALT_PAGE = 0x9A6E
SALT_GATE = 0x6A7E
SALT_OFF = 0x0FF5
SALT_WRITE = 0x3717

KINDS = ("zipfian", "hotspot", "bursty", "scan")
DEFAULTS = {"page_bytes": 4096, "line_offsets": 64, "write_frac": 0.3,
            "zipf_s": 1.0, "hot_frac": 0.9, "hot_pages": 0, "on_len": 64,
            "off_len": 192, "cold_stride": 17, "stride_pages": 1}


def _mix(x: int) -> int:
    x = (x + GOLDEN) & M64
    x = ((x ^ (x >> 30)) * MULT1) & M64
    x = ((x ^ (x >> 27)) * MULT2) & M64
    return x ^ (x >> 31)


def _mix_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64) + np.uint64(GOLDEN)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(MULT1)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(MULT2)
    return x ^ (x >> np.uint64(31))


def hash_np(seed: int, salt: int, a: int, b: np.ndarray) -> np.ndarray:
    """64-bit decision hash over ``(seed, salt, a, b)``, vectorized in b."""
    h1 = _mix(_mix((seed + salt) & M64) ^ (a & M64))
    return _mix_np(np.uint64(h1) ^ np.asarray(b).astype(np.uint64))


def rate_threshold(rate: float) -> int:
    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"rate must be in [0, 1], got {rate}")
    return min(1 << 32, int(rate * (1 << 32)))


def zipf_cdf(num_pages: int, s: float) -> np.ndarray:
    w = 1.0 / np.power(np.arange(1, num_pages + 1, dtype=np.float64), s)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def _u01(h):
    return (h >> np.uint64(11)) * (2.0 ** -53)


def params(spec: dict) -> dict:
    """The generator parameters of a traffic file's ``generator`` block,
    defaults filled in and checked."""
    p = {**DEFAULTS, **spec}
    if p["kind"] not in KINDS:
        raise ValueError(f"unknown generator kind {p['kind']!r}")
    if p["num_pages"] < 2:
        raise ValueError("generator needs a footprint of >= 2 pages")
    if p["hot_pages"] == 0:
        p["hot_pages"] = max(1, p["num_pages"] // 16)
    return p


def host_trace(spec: dict, seed: int, host: int, n: int):
    """``(addrs int64 (n,), writes bool (n,))`` of one host's trace."""
    p = params(spec)
    kind, pages = p["kind"], p["num_pages"]
    idx = np.arange(n, dtype=np.int64)
    h = hash_np(seed, SALT_PAGE, host, idx)
    if kind == "zipfian":
        page = np.minimum(
            np.searchsorted(zipf_cdf(pages, p["zipf_s"]), _u01(h),
                            side="right"), pages - 1).astype(np.int64)
    elif kind == "hotspot":
        hot = (hash_np(seed, SALT_GATE, host, idx) & np.uint64(M32)) \
            < np.uint64(rate_threshold(p["hot_frac"]))
        hp = p["hot_pages"]
        page = np.where(hot, h % np.uint64(hp),
                        np.uint64(hp) + h % np.uint64(pages - hp)
                        ).astype(np.int64)
    elif kind == "bursty":
        on = idx % (p["on_len"] + p["off_len"]) < p["on_len"]
        page = np.where(on, (h % np.uint64(p["hot_pages"])).astype(
            np.int64), (idx * p["cold_stride"]) % pages)
    else:
        page = (idx * p["stride_pages"]) % pages
    off = (hash_np(seed, SALT_OFF, host, idx)
           % np.uint64(p["line_offsets"])).astype(np.int64)
    wr = (hash_np(seed, SALT_WRITE, host, idx) & np.uint64(M32)) \
        < np.uint64(rate_threshold(p["write_frac"]))
    return page * p["page_bytes"] + off * 64, wr
