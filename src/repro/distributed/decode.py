"""Flash-decoding with the KV-cache SEQUENCE axis sharded over the model
axis (SP-for-decode).

Why: at decode_32k (batch 128, 32 k context) the KV cache of a GQA model
like glm4 is ~170 GB — it only fits if *both* batch (data axis) and
sequence (model axis) shard.  Head-sharding cannot help (kv_heads=2 < 16).
Each device holds a contiguous slot-range of the ring buffer, computes a
partial softmax over its shard, and the exact result is reconstructed with
a max/sum merge (pmax + psum) — the same math as
:func:`repro.kernels.flash_decode.combine_partials`, validated against it.

Per layer the collectives are tiny (q/k/v all-gathers of a single token's
projections + two psums of (B, H, hd)), while the big KV tensor never
moves — that is the point.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import apply_rope

NEG_INF = -1e30


def _partial_attn(q, kc, vc, n_valid_local):
    """q: (B, H, hd); kc/vc: (B, S_loc, KV, hd); n_valid_local: () int32.
    Returns locally-normalized (out, m, l) partial-softmax stats."""
    B, S_loc, KV, hd = kc.shape
    H = q.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bckd->bkgc", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) * hd ** -0.5
    valid = jnp.arange(S_loc)[None, :] < n_valid_local
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    out = jnp.einsum("bkgc,bckd->bkgd", p, vc.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-37)[..., None]
    return (out.reshape(B, H, hd), m.reshape(B, H), l.reshape(B, H))


def decode_attn_sharded(x, blk, cfg: ArchConfig, k_cache, v_cache, cur, ctx,
                        k_scale=None, v_scale=None):
    """One decode-attention layer under shard_map.

    x: (B, D) [batch over dp unless ctx.batch_replicated];
    k_cache/v_cache: (B, Sc, KV, hd) with Sc sharded over tp;
    cur: () int32 global token position.
    Returns (y (B, D), new_k_cache, new_v_cache).
    """
    mesh, tp, dp = ctx.mesh, ctx.tp_axis, ctx.dp_axes
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    b = None if ctx.batch_replicated else dp
    Sc = k_cache.shape[1]
    n_tp = mesh.shape[tp]
    quant = k_scale is not None

    def body(xl, wq, wk, wv, wo, kc, vc, cur, ks=None, vs=None):
        Bl, D = xl.shape
        S_loc = kc.shape[1]
        tpi = jax.lax.axis_index(tp)

        # --- projections (column-sharded) -> assemble full heads
        q = jax.lax.all_gather(xl @ wq, tp, axis=1, tiled=True).reshape(Bl, H, hd)
        kn = jax.lax.all_gather(xl @ wk, tp, axis=1, tiled=True).reshape(Bl, KV, hd)
        vn = jax.lax.all_gather(xl @ wv, tp, axis=1, tiled=True).reshape(Bl, KV, hd)
        pos = jnp.full((Bl, 1), cur)
        q = apply_rope(q[:, None], pos, cfg.rope_theta)[:, 0]
        kn = apply_rope(kn[:, None], pos, cfg.rope_theta)[:, 0]

        # --- ring-buffer write: only the owning shard stores the new KV
        slot = jax.lax.rem(cur, Sc)
        local_slot = slot - tpi * S_loc
        sel = (jnp.arange(S_loc)[None, :, None, None] == local_slot)
        if quant:
            from repro.models.transformer import _quantize_kv
            kq, ksn = _quantize_kv(kn.astype(jnp.float32))
            vq, vsn = _quantize_kv(vn.astype(jnp.float32))
            kc = jnp.where(sel, kq[:, None], kc)
            vc = jnp.where(sel, vq[:, None], vc)
            ks = jnp.where(sel[..., 0], ksn[:, None], ks)
            vs = jnp.where(sel[..., 0], vsn[:, None], vs)
            k_eff = kc.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
            v_eff = vc.astype(jnp.float32) * vs.astype(jnp.float32)[..., None]
        else:
            kc = jnp.where(sel, kn[:, None].astype(kc.dtype), kc)
            vc = jnp.where(sel, vn[:, None].astype(vc.dtype), vc)
            k_eff, v_eff = kc, vc

        # --- local partial attention over my slot range
        n_valid = jnp.minimum(cur + 1, Sc)
        n_local = jnp.clip(n_valid - tpi * S_loc, 0, S_loc)
        out, m, l = _partial_attn(q, k_eff, v_eff, n_local)

        # --- exact softmax merge across the tp axis
        m_g = jax.lax.pmax(m, tp)
        w = jnp.exp(m - m_g) * l
        denom = jax.lax.psum(w, tp)
        num = jax.lax.psum(out * w[..., None], tp)
        out = num / jnp.maximum(denom, 1e-37)[..., None]

        # --- output projection: my head slice x row-sharded wo, psum
        h_loc = (H * hd) // n_tp
        mine = jax.lax.dynamic_slice_in_dim(out.reshape(Bl, H * hd),
                                            tpi * h_loc, h_loc, 1)
        y = jax.lax.psum(mine.astype(wo.dtype) @ wo, tp)
        if quant:
            return y.astype(xl.dtype), kc, vc, ks, vs
        return y.astype(xl.dtype), kc, vc

    base_in = (P(b, None), P(None, tp), P(None, tp), P(None, tp),
               P(tp, None), P(b, tp, None, None), P(b, tp, None, None), P())
    base_out = (P(b, None), P(b, tp, None, None), P(b, tp, None, None))
    if quant:
        return shard_map(
            body, mesh=mesh,
            in_specs=base_in + (P(b, tp, None), P(b, tp, None)),
            out_specs=base_out + (P(b, tp, None), P(b, tp, None)),
            check_vma=False,
        )(x, blk["wq"], blk["wk"], blk["wv"], blk["wo"], k_cache, v_cache,
          cur, k_scale, v_scale)
    return shard_map(
        body, mesh=mesh,
        in_specs=base_in,
        out_specs=base_out,
        check_vma=False,
    )(x, blk["wq"], blk["wk"], blk["wv"], blk["wo"], k_cache, v_cache, cur)
