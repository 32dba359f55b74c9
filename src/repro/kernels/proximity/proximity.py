"""Pallas TPU kernel: fused toroidal-distance + range test + LP histogram.

The paper's hot spot is O(N^2) proximity interaction matching (§5.1);
this kernel tiles SEs into (BI x BJ) blocks held in VMEM, computes the
wrapped per-axis deltas on the VPU, and accumulates the per-sender LP
histogram as a masked (BI x BJ) @ (BJ x L) matmul on the MXU — so the
histogram reduction rides the systolic array rather than scatter units
(the GPU-native formulation would use atomics; see DESIGN.md
§Adaptations).

Grid: (N/BI, N/BJ); the j-loop is the innermost (sequential) dim so the
accumulator tile stays resident in VMEM across the whole j sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BI = 256  # tile side: sender rows and recipient cols share it


def _kernel(pos_i_ref, pos_j_ref, lp_onehot_ref, sender_ref, iota_i_ref,
            iota_j_ref, out_ref, *, area: float, rng2: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pi = pos_i_ref[...]  # (BI, 2)
    pj = pos_j_ref[...]  # (BJ, 2)
    dx = jnp.abs(pi[:, 0:1] - pj[:, 0:1].T)  # (BI, BJ)
    dy = jnp.abs(pi[:, 1:2] - pj[:, 1:2].T)
    dx = jnp.minimum(dx, area - dx)
    dy = jnp.minimum(dy, area - dy)
    within = (dx * dx + dy * dy) <= rng2
    not_self = iota_i_ref[...][:, 0:1] != iota_j_ref[...][:, 0:1].T
    sender = sender_ref[...][:, 0:1] != 0
    mask = (within & not_self & sender).astype(jnp.float32)
    # LP histogram on the MXU: (BI,BJ) @ (BJ,L)
    out_ref[...] += jnp.dot(mask, lp_onehot_ref[...],
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_lp", "area", "rng",
                                             "interpret"))
def proximity_lp_counts(pos, lp, sender_mask, n_lp: int, area: float,
                        rng: float, interpret: bool):
    n = pos.shape[0]
    bi = bj = min(BI, n)
    pad = -n % bi
    # pad to a whole number of tiles: padded recipients get lp = -1 (an
    # all-zero one-hot row, so they never count); padded senders are 0
    pos = jnp.pad(pos, ((0, pad), (0, 0)))
    lp = jnp.pad(lp, (0, pad), constant_values=-1)
    sender_mask = jnp.pad(sender_mask, (0, pad))
    np_ = n + pad
    lp_pad = max(n_lp, 8)
    onehot = jax.nn.one_hot(lp, lp_pad, dtype=jnp.float32)
    iota = jnp.arange(np_, dtype=jnp.int32)[:, None]
    sender = sender_mask.astype(jnp.int32)[:, None]

    out = pl.pallas_call(
        functools.partial(_kernel, area=float(area), rng2=float(rng) ** 2),
        grid=(np_ // bi, np_ // bj),
        in_specs=[
            pl.BlockSpec((bi, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bj, 2), lambda i, j: (j, 0)),
            pl.BlockSpec((bj, lp_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((bi, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bi, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bj, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bi, lp_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, lp_pad), jnp.float32),
        interpret=interpret,
    )(pos, pos, onehot, sender, iota, iota)
    return out[:n, :n_lp].astype(jnp.int32)
