"""Mixture-of-Experts layer: top-k routing, capacity-based sort-free
dispatch, and two sharding modes.

``ep``  (kimi-k2: 384 experts): experts sharded over the ``model`` axis;
        tokens routed with a tiled ``all_to_all`` inside ``shard_map``
        (24 experts/device on a 16-wide model axis).
``tp``  (mixtral: 8 experts < axis): every device holds all experts but only
        a ``d_expert/axis`` slice; partial outputs are ``psum``-reduced.
        No all_to_all — the dispatch stays device-local.

Dispatch is gather-based with a fixed per-expert capacity
(``ceil(T*K/E * capacity_factor)``); overflow tokens are dropped (they ride
the residual), underflow slots are masked.  This keeps every shape static —
a requirement for the multi-pod dry-run — and matches standard TPU MoE
practice (Switch/GShard capacity dispatch).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.base import MoEConfig


class MoEParams(NamedTuple):
    router: jnp.ndarray        # (D, E)
    w_gate: jnp.ndarray        # (E, D, F)
    w_up: jnp.ndarray          # (E, D, F)
    w_down: jnp.ndarray        # (E, F, D)


def init_moe_params(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> MoEParams:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    E, F = cfg.n_experts, cfg.d_expert
    s_in = d_model ** -0.5
    s_out = F ** -0.5
    return MoEParams(
        router=(jax.random.normal(k1, (d_model, E)) * s_in).astype(dtype),
        w_gate=(jax.random.normal(k2, (E, d_model, F)) * s_in).astype(dtype),
        w_up=(jax.random.normal(k3, (E, d_model, F)) * s_in).astype(dtype),
        w_down=(jax.random.normal(k4, (E, F, d_model)) * s_out).astype(dtype),
    )


def capacity_for(tokens: int, cfg: MoEConfig,
                 factor: Optional[float] = None) -> int:
    f = cfg.capacity_factor if factor is None else factor
    return max(1, math.ceil(tokens * cfg.top_k / cfg.n_experts * f))


def _route(x, router, top_k: int):
    """x: (T, D) -> (weights (T,K), expert_idx (T,K), aux_loss scalar)."""
    logits = (x.astype(jnp.float32)) @ router.astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    E = router.shape[1]
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * mean_prob)
    return weights, idx, aux


def _dispatch_indices(expert_idx, n_experts: int, capacity: int):
    """Flat assignment list -> (per-expert slot matrix, validity mask).

    Returns ``slots (E, C)`` holding flat assignment ids (t*K + k) and
    ``valid (E, C)``.  Sort-free: assignments are ranked within their expert
    by a stable argsort of expert id."""
    TK = expert_idx.size
    flat = expert_idx.reshape(-1)                      # (T*K,)
    order = jnp.argsort(flat, stable=True)             # grouped by expert
    counts = jnp.bincount(flat, length=n_experts)      # (E,)
    start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                             jnp.cumsum(counts)[:-1]])
    pos = start[:, None] + jnp.arange(capacity)[None, :]        # (E, C)
    valid = jnp.arange(capacity)[None, :] < jnp.minimum(counts, capacity)[:, None]
    slots = jnp.take(order, jnp.clip(pos, 0, TK - 1), axis=0)
    return slots, valid


def _expert_ffn(xe, w_gate, w_up, w_down):
    """xe: (E, C, D) grouped tokens -> (E, C, D)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_ffn_local(x, p: MoEParams, cfg: MoEConfig,
                  capacity_factor: Optional[float] = None):
    """Single-device MoE: x (T, D) -> (T, D), aux_loss."""
    T, D = x.shape
    weights, idx, aux = _route(x, p.router, cfg.top_k)
    C = capacity_for(T, cfg, capacity_factor)
    slots, valid = _dispatch_indices(idx, cfg.n_experts, C)
    token_of = slots // cfg.top_k                                  # (E, C)
    xe = jnp.take(x, token_of, axis=0) * valid[..., None]          # (E, C, D)
    ye = _expert_ffn(xe.astype(x.dtype), p.w_gate, p.w_up, p.w_down)
    w_flat = weights.reshape(-1)                                   # (T*K,)
    wslot = jnp.take(w_flat, slots) * valid                        # (E, C)
    out = jnp.zeros((T, D), ye.dtype).at[token_of.reshape(-1)].add(
        (ye * wslot[..., None]).reshape(-1, D))
    return out.astype(x.dtype), aux


def moe_ffn_sharded(x, p: MoEParams, cfg: MoEConfig, mesh,
                    dp_axes: Tuple[str, ...], tp_axis: str,
                    capacity_factor: Optional[float] = None,
                    batch_replicated: bool = False,
                    resident_experts: bool = False):
    """Sharded MoE over a (dp..., tp) mesh.  x: (B, S, D) with B sharded over
    ``dp_axes`` (or replicated).  Expert placement per ``cfg.sharding``.

    ``resident_experts=True`` is the DECODE layout (§Perf hillclimb): expert
    weights stay resident, sharded (experts over tp) x (expert-hidden over
    dp); the few decode tokens are all-gathered instead of the multi-GB
    expert weights — the collective per layer drops from O(expert bytes) to
    O(token bytes)."""
    B, S, D = x.shape
    n_tp = mesh.shape[tp_axis]
    dp_spec = None if batch_replicated else dp_axes
    dp_axes = () if batch_replicated else dp_axes

    if resident_experts and cfg.sharding == "ep" and n_tp > 1:
        e_spec_f = P(tp_axis, None, None)  # placeholder replaced below

        def body_res(xl, router, w_gate, w_up, w_down):
            # xl: (B_loc, S, D); weights: (E_loc, D, F_loc)
            T_loc = xl.shape[0] * xl.shape[1]
            xf = xl.reshape(T_loc, D)
            # gather ALL tokens (tiny at decode) so every device can serve
            # its resident expert shard
            for axn in dp_axes:
                xf = jax.lax.all_gather(xf, axn, axis=0, tiled=True)
            T = xf.shape[0]
            weights, idx, aux = _route(xf, router, cfg.top_k)
            C = capacity_for(T, cfg, capacity_factor)
            slots, valid = _dispatch_indices(idx, cfg.n_experts, C)
            token_of = slots // cfg.top_k
            e_loc = w_gate.shape[0]
            tpi = jax.lax.axis_index(tp_axis)
            my_slots = jax.lax.dynamic_slice_in_dim(slots, tpi * e_loc, e_loc, 0)
            my_valid = jax.lax.dynamic_slice_in_dim(valid, tpi * e_loc, e_loc, 0)
            my_tok = my_slots // cfg.top_k
            xe = jnp.take(xf, my_tok, axis=0) * my_valid[..., None]
            ye = _expert_ffn(xe.astype(xf.dtype), w_gate, w_up, w_down)
            # F is sharded over dp -> partial sums; tokens identical on all
            # dp shards, so psum over dp completes the contraction
            for axn in dp_axes:
                ye = jax.lax.psum(ye, axn)
            w_flat = weights.reshape(-1)
            wslot = jnp.take(w_flat, my_slots) * my_valid
            out = jnp.zeros((T, D), jnp.float32).at[my_tok.reshape(-1)].add(
                (ye.astype(jnp.float32) * wslot[..., None]).reshape(-1, D))
            out = jax.lax.psum(out, tp_axis)   # combine expert shards
            # keep my dp slice of the tokens
            if dp_axes:
                dpi = jax.lax.axis_index(dp_axes[0])
                for axn in dp_axes[1:]:
                    dpi = dpi * mesh.shape[axn] + jax.lax.axis_index(axn)
                out = jax.lax.dynamic_slice_in_dim(out, dpi * T_loc, T_loc, 0)
            aux = jax.lax.pmean(aux, tp_axis)
            return out.reshape(xl.shape).astype(xl.dtype), aux

        return shard_map(
            body_res, mesh=mesh,
            in_specs=(P(dp_spec, None, None), P(None, None),
                      P(tp_axis, None, dp_axes or None),
                      P(tp_axis, None, dp_axes or None),
                      P(tp_axis, dp_axes or None, None)),
            out_specs=(P(dp_spec, None, None), P()),
            check_vma=False,
        )(x, p.router, p.w_gate, p.w_up, p.w_down)

    if cfg.sharding == "ep" and cfg.n_experts % n_tp == 0 and n_tp > 1:
        e_spec = P(tp_axis, None, None)

        def body(xl, router, w_gate, w_up, w_down):
            T = xl.shape[0] * xl.shape[1]
            xf = xl.reshape(T, D)
            weights, idx, aux = _route(xf, router, cfg.top_k)
            C = capacity_for(T, cfg, capacity_factor)
            slots, valid = _dispatch_indices(idx, cfg.n_experts, C)
            token_of = slots // cfg.top_k
            xe = jnp.take(xf, token_of, axis=0) * valid[..., None]  # (E, C, D)
            # send each expert block to its owner: (E, C, D) -> (E, C, D)
            # where rows now hold **my local experts'** tokens from every src
            xr = jax.lax.all_to_all(xe.astype(xf.dtype), tp_axis, 0, 0, tiled=True)
            e_loc = cfg.n_experts // n_tp
            xr = xr.reshape(n_tp, e_loc, C, D).transpose(1, 0, 2, 3) \
                   .reshape(e_loc, n_tp * C, D)
            yr = _expert_ffn(xr, w_gate, w_up, w_down)
            yr = yr.reshape(e_loc, n_tp, C, D).transpose(1, 0, 2, 3) \
                   .reshape(cfg.n_experts, C, D)
            ye = jax.lax.all_to_all(yr, tp_axis, 0, 0, tiled=True)
            w_flat = weights.reshape(-1)
            wslot = jnp.take(w_flat, slots) * valid
            out = jnp.zeros((T, D), jnp.float32).at[token_of.reshape(-1)].add(
                (ye.astype(jnp.float32) * wslot[..., None]).reshape(-1, D))
            aux = jax.lax.pmean(aux, tp_axis)
            for ax in dp_axes:
                aux = jax.lax.pmean(aux, ax)
            return out.reshape(xl.shape).astype(xl.dtype), aux

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(dp_spec, None, None), P(None, None),
                      e_spec, e_spec, P(tp_axis, None, None)),
            out_specs=(P(dp_spec, None, None), P()),
            check_vma=False,
        )(x, p.router, p.w_gate, p.w_up, p.w_down)

    # 'tp' mode: experts replicated, d_expert sharded; psum partial outputs.
    def body_tp(xl, router, w_gate, w_up, w_down):
        T = xl.shape[0] * xl.shape[1]
        xf = xl.reshape(T, D)
        weights, idx, aux = _route(xf, router, cfg.top_k)
        C = capacity_for(T, cfg, capacity_factor)
        slots, valid = _dispatch_indices(idx, cfg.n_experts, C)
        token_of = slots // cfg.top_k
        xe = jnp.take(xf, token_of, axis=0) * valid[..., None]
        ye = _expert_ffn(xe.astype(xf.dtype), w_gate, w_up, w_down)
        ye = jax.lax.psum(ye, tp_axis)               # reduce over F shards
        w_flat = weights.reshape(-1)
        wslot = jnp.take(w_flat, slots) * valid
        out = jnp.zeros((T, D), jnp.float32).at[token_of.reshape(-1)].add(
            (ye.astype(jnp.float32) * wslot[..., None]).reshape(-1, D))
        aux = jax.lax.pmean(aux, tp_axis)
        for ax in dp_axes:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(xl.shape).astype(xl.dtype), aux

    return shard_map(
        body_tp, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, None),
                  P(None, None, tp_axis), P(None, None, tp_axis),
                  P(None, tp_axis, None)),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )(x, p.router, p.w_gate, p.w_up, p.w_down)
