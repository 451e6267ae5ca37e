"""jit'd public wrappers for the Pallas kernels.

``interpret`` is True on CPU (kernel bodies execute in Python for
validation) and False on TPU, where the kernels compile to Mosaic; any
other backend is refused (:func:`interpret_mode`).
"""

from __future__ import annotations

import jax

from repro.kernels.cache_sim import cache_sim
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.flash_decode import combine_partials, flash_decode_tpu
from repro.kernels.page_gather import page_gather, page_scatter


def interpret_mode() -> bool:
    """Interpret on the CPU backend, compile on a TPU.  Any other backend
    raises: a kernel is never interpreted on an accelerator in silence."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
            f"backend {backend!r} has neither mode")
    return backend == "cpu"


def cache_sim_op(pages, writes, *, num_sets, ways, policy="lru", chunk=1024):
    return cache_sim(pages, writes, num_sets=num_sets, ways=ways,
                     policy=policy, chunk=chunk,
                     interpret=interpret_mode())


def flash_attention_op(q, k, v, *, causal=True, window=0, bq=128, bk=128):
    return flash_attention_tpu(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=interpret_mode())


def flash_decode_op(q, k_cache, v_cache, n_valid, *, bk=512):
    return flash_decode_tpu(q, k_cache, v_cache, n_valid, bk=bk,
                            interpret=interpret_mode())


def _as3d(x):
    """Kernels address pages as (P, R, C); flatten any trailing page shape."""
    if x.ndim == 3:
        return x, None
    shape = x.shape
    r = shape[1] if x.ndim > 1 else 1
    c = 1
    for d in shape[2:]:
        c *= d
    return x.reshape(shape[0], r, max(c, 1)), shape


def page_gather_op(pool, table):
    pool3, orig = _as3d(pool)
    out = page_gather(pool3, table, interpret=interpret_mode())
    if orig is not None:
        out = out.reshape((out.shape[0],) + orig[1:])
    return out


def page_scatter_op(pool, table, pages):
    pool3, orig = _as3d(pool)
    pages3, _ = _as3d(pages)
    out = page_scatter(pool3, table, pages3, interpret=interpret_mode())
    return out.reshape(orig) if orig is not None else out


__all__ = ["interpret_mode", "cache_sim_op", "flash_attention_op",
           "flash_decode_op", "combine_partials", "page_gather_op",
           "page_scatter_op"]
