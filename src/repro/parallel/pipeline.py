"""Pipeline parallelism (GPipe schedule) over a mesh axis.

For the multi-pod mesh the natural stage axis is "pod": each pod holds a
contiguous slice of layers, microbatches stream across the (slow)
inter-pod links via collective_permute, and the bubble fraction is
(P-1)/(P-1+M). Expressed with shard_map: the stage body runs its local
layer slice; `ppermute` hands activations to the next stage.

This module is the library feature + tests; the default dry-run configs
use pod-axis data parallelism (better MFU at 2 pods — see DESIGN.md
§Parallelism for the trade-off), and the trainer can opt in with
--pipeline pod.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(stage_fn: Callable, mesh, axis: str, n_micro: int):
    """Build a pipelined forward: y = stages(x), stages split over `axis`.

    stage_fn(stage_params, x) -> y applies ONE stage's layers.
    Inputs: stage_params pytree with leading stage dim (sharded over
    `axis`); x (n_micro, B_m, ...) replicated. Output replicated.
    """
    n_stage = mesh.shape[axis]

    def body(params, xs):
        stage = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params)  # this stage's slice
        n_ticks = n_micro + n_stage - 1
        buf = jnp.zeros_like(xs[0])  # current activation holding slot
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when valid)
            take = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(stage == 0,
                               jnp.float32(t < n_micro), 0.0)
            x_in = jnp.where(inject > 0, xs[take], buf)
            y = stage_fn(params, x_in)
            # pass activations down the pipe
            perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            nxt = jax.lax.ppermute(y, axis, perm)
            # last stage emits microbatch t - (n_stage - 1)
            emit_idx = jnp.clip(t - (n_stage - 1), 0, n_micro - 1)
            is_emit = jnp.logical_and(stage == n_stage - 1,
                                      t >= n_stage - 1)
            outs = jax.lax.cond(
                is_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, emit_idx, 0),
                lambda o: o, outs)
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(n_ticks))
        # broadcast results from the last stage to everyone
        outs = jax.lax.psum(
            jnp.where(stage == n_stage - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    in_specs = (P(axis), P())
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)
