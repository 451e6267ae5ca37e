"""Pipeline parallelism over the ``pod`` axis (GPipe schedule).

On the 2x16x16 multi-pod mesh the default plan is DP over pods (gradients
all-reduce over the slow inter-pod links once per step).  When activations
are smaller than gradients — deep-narrow models or large accumulation — the
better plan is to split LAYERS across pods and stream microbatches
(activations cross pods instead of gradients).  This module implements that
alternative: stages = pods, ``collective_permute`` moves activations
stage->stage, and microbatches keep all stages busy (GPipe; bubble fraction
= (P-1)/(P-1+M)).

Implemented with ``shard_map`` over the ``pod`` axis: every pod runs the
same program on its layer slice; non-stage-0 inputs are ignored, partial
outputs stream forward.  Works for any per-layer ``block_fn(x, blk) -> x``.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_forward(block_fn: Callable, mesh, *, stage_axis: str = "pod",
                     microbatches: int = 4):
    """Returns ``fn(x, stacked_blocks) -> y`` running layers split across
    ``stage_axis`` with GPipe microbatching.

    x: (B, ...) activations (B % microbatches == 0);
    stacked_blocks: pytree stacked on a leading n_layers axis with
    n_layers % n_stages == 0 (each stage takes a contiguous slice).
    """
    n_stages = mesh.shape[stage_axis]

    def staged(x, blocks):
        stage = jax.lax.axis_index(stage_axis)
        B = x.shape[0]
        mb = B // microbatches
        xs = x.reshape(microbatches, mb, *x.shape[1:])

        def run_stage(xmb):
            def body(h, blk):
                return block_fn(h, blk), None
            h, _ = jax.lax.scan(body, xmb, blocks)
            return h

        n_ticks = microbatches + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range); others take the
            # previous stage's output that arrived last tick
            mb_idx = jnp.clip(t, 0, microbatches - 1)
            inject = jnp.where(stage == 0,
                               jnp.ones((), jnp.bool_), jnp.zeros((), jnp.bool_))
            x_in = jnp.where(inject & (t < microbatches), xs[mb_idx], buf)
            y = run_stage(x_in)
            # pass forward: stage i -> stage i+1 (last stage wraps to 0,
            # but its payload is only consumed as output)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf_next = jax.lax.ppermute(y, stage_axis, perm)
            # last stage records finished microbatch (t - (n_stages-1))
            done_idx = t - (n_stages - 1)
            is_done = (done_idx >= 0) & (done_idx < microbatches) & \
                      (stage == n_stages - 1)
            outs = jax.lax.cond(
                is_done,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y[None], jnp.clip(done_idx, 0, microbatches - 1), 0),
                lambda o: o, outs)
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all pods (masked
        # psum — ppermute cannot fan out one source to many destinations)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs.reshape(B, *x.shape[1:])

    def fn(x, stacked_blocks):
        return shard_map(
            staged, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: P(stage_axis),
                                        stacked_blocks)),
            out_specs=P(),
            check_vma=False,
        )(x, stacked_blocks)

    return fn
