"""Pallas kernel validation: shape/dtype sweeps, allclose vs ref.py oracles
(interpret mode executes kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (cache_sim_op, combine_partials,
                               flash_attention_op, flash_decode_op,
                               page_gather_op, page_scatter_op)

KEY = jax.random.PRNGKey(7)


# ------------------------------------------------------------- cache_sim
class TestCacheSimKernel:
    @pytest.mark.parametrize("policy,num_sets,ways",
                             [("lru", 32, 4), ("lru", 64, 8), ("lru", 1, 16),
                              ("fifo", 32, 4), ("fifo", 16, 2),
                              ("direct", 64, 1)])
    def test_matches_oracle(self, policy, num_sets, ways):
        rng = np.random.default_rng(11)
        n = 1500
        pages = jnp.asarray(rng.integers(0, num_sets * ways * 3, size=n),
                            jnp.int32)
        writes = jnp.asarray(rng.random(n) < 0.4)
        h, e = cache_sim_op(pages, writes, num_sets=num_sets, ways=ways,
                            policy=policy, chunk=1024)
        hr, er = ref.cache_sim_ref(pages, writes, num_sets=num_sets,
                                   ways=ways, policy=policy)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(hr))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(er))

    def test_non_multiple_chunk_padding(self):
        rng = np.random.default_rng(5)
        n = 777  # not a multiple of chunk
        pages = jnp.asarray(rng.integers(0, 256, size=n), jnp.int32)
        writes = jnp.asarray(rng.random(n) < 0.5)
        h, e = cache_sim_op(pages, writes, num_sets=16, ways=4, chunk=1024)
        hr, er = ref.cache_sim_ref(pages, writes, num_sets=16, ways=4)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(hr))

    def test_rejects_unsupported(self):
        pages = jnp.zeros((8,), jnp.int32)
        with pytest.raises(ValueError):
            cache_sim_op(pages, pages, num_sets=4, ways=2, policy="2q")
        with pytest.raises(ValueError):
            cache_sim_op(pages, pages, num_sets=4, ways=2, policy="direct")


# -------------------------------------------------------- flash_attention
class TestFlashAttentionKernel:
    @pytest.mark.parametrize("S,H,KV,hd,win,dtype", [
        (64, 4, 4, 32, 0, jnp.float32),
        (96, 8, 2, 16, 0, jnp.float32),
        (64, 4, 4, 32, 24, jnp.float32),
        (70, 4, 2, 32, 0, jnp.float32),       # padded seq
        (64, 4, 4, 32, 0, jnp.bfloat16),
    ])
    def test_causal_matches_ref(self, S, H, KV, hd, win, dtype):
        q = jax.random.normal(KEY, (2, S, H, hd), dtype)
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, S, KV, hd), dtype)
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, S, KV, hd), dtype)
        out = flash_attention_op(q, k, v, causal=True, window=win, bq=32, bk=32)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=win)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_cross_attention_lengths(self):
        q = jax.random.normal(KEY, (2, 48, 4, 32))
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 20, 2, 32))
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 20, 2, 32))
        out = flash_attention_op(q, k, v, causal=False, bq=16, bk=16)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_model_layer_path(self):
        """Kernel and the pure-JAX scan attention agree (same numerics)."""
        from repro.models.layers import flash_attention as scan_attn
        q = jax.random.normal(KEY, (1, 64, 8, 32))
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 64, 8, 32))
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 64, 8, 32))
        a = flash_attention_op(q, k, v, bq=32, bk=32)
        b = scan_attn(q, k, v, causal=True, q_block=32, kv_block=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------- flash_decode
class TestFlashDecodeKernel:
    @pytest.mark.parametrize("Smax,H,KV,hd,n_valid", [
        (128, 8, 8, 32, 128), (128, 8, 2, 32, 77), (256, 4, 4, 16, 1),
        (96, 16, 4, 64, 50),
    ])
    def test_matches_ref(self, Smax, H, KV, hd, n_valid):
        B = 2
        q = jax.random.normal(KEY, (B, H, hd))
        kc = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Smax, KV, hd))
        vc = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Smax, KV, hd))
        out, m, l = flash_decode_op(q, kc, vc, n_valid, bk=32)
        want, mw, lw = ref.flash_decode_ref(q, kc, vc, n_valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(m), np.asarray(mw), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l), np.asarray(lw), rtol=1e-4, atol=1e-5)

    def test_sharded_combine_exact(self):
        """Splitting the KV cache into shards + combine == unsharded result."""
        B, Smax, H, KV, hd, n_shards = 2, 128, 8, 4, 32, 4
        q = jax.random.normal(KEY, (B, H, hd))
        kc = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Smax, KV, hd))
        vc = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Smax, KV, hd))
        full, _, _ = flash_decode_op(q, kc, vc, Smax, bk=32)
        S_loc = Smax // n_shards
        outs, ms, ls = [], [], []
        for i in range(n_shards):
            o, m, l = flash_decode_op(q, kc[:, i*S_loc:(i+1)*S_loc],
                                      vc[:, i*S_loc:(i+1)*S_loc], S_loc, bk=32)
            outs.append(o); ms.append(m); ls.append(l)
        merged = combine_partials(jnp.stack(outs), jnp.stack(ms), jnp.stack(ls))
        np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ page gather
class TestPageGatherKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
    def test_gather(self, dtype):
        pool = jnp.arange(16 * 8 * 32).reshape(16, 8, 32).astype(dtype)
        table = jnp.asarray([3, 0, 15, 7, 7], jnp.int32)
        out = page_gather_op(pool, table)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(ref.page_gather_ref(pool, table), np.float32))

    def test_scatter(self):
        pool = jnp.zeros((8, 4, 16), jnp.float32)
        table = jnp.asarray([2, 5], jnp.int32)
        pages = jnp.ones((2, 4, 16), jnp.float32)
        out = page_scatter_op(pool, table, pages)
        want = ref.page_scatter_ref(jnp.zeros((8, 4, 16)), table, pages)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_gather_roundtrip_scatter(self):
        pool = jax.random.normal(KEY, (12, 4, 8))
        table = jnp.asarray([1, 4, 9], jnp.int32)
        pages = page_gather_op(pool, table)
        restored = page_scatter_op(pool, table, pages)
        np.testing.assert_allclose(np.asarray(restored), np.asarray(pool))
