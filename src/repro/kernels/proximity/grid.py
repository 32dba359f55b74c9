"""Pallas TPU kernel: proximity LP histogram over cell-list candidates.

The dense kernel (proximity.py) sweeps all N^2 pairs; this one only sees
the cell-list candidates produced by core/neighbors.py — each sender's
3x3 neighborhood, a (N, C) gather with C = 9 * cell capacity — so the
work drops from O(N^2) to O(N*C).

The jnp side does the binning and the candidate gather (sort-by-cell is
a global data movement XLA already does well); the kernel fuses what is
per-pair: wrapped per-axis deltas, the range test, validity/sender
masking, and the per-sender LP histogram. Unlike the dense kernel the
histogram cannot ride the MXU here — candidate LPs differ per *row*, so
there is no shared (BJ, L) one-hot operand — instead the kernel keeps
the candidate LP tile in VMEM and does L masked VPU reductions (L is
tiny: the paper uses 4–9 LPs). See DESIGN.md §Adaptations.

Grid: (N/BI, C/BC); the candidate-tile loop is the innermost
(sequential) dim so each sender tile's accumulator stays resident in
VMEM across its whole candidate sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.neighbors import GridSpec, candidate_table

BI = 256  # sender tile (rows)
BC = 256  # candidate tile (cols)


def _kernel(px_ref, py_ref, sender_ref, cx_ref, cy_ref, clp_ref, valid_ref,
            out_ref, *, area: float, rng2: float, n_lp_pad: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dx = jnp.abs(px_ref[...] - cx_ref[...])  # (BI, BC)
    dy = jnp.abs(py_ref[...] - cy_ref[...])
    dx = jnp.minimum(dx, area - dx)
    dy = jnp.minimum(dy, area - dy)
    within = (dx * dx + dy * dy) <= rng2
    mask = (within.astype(jnp.float32) * valid_ref[...]
            * sender_ref[...])  # (BI, BC) in {0, 1}
    clp = clp_ref[...]
    # per-row candidate LPs -> no shared one-hot operand for the MXU;
    # L masked VPU reductions instead (L is single-digit)
    cols = [jnp.sum(mask * (clp == l), axis=1, keepdims=True)
            for l in range(n_lp_pad)]
    out_ref[...] += jnp.concatenate(cols, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("n_lp", "area", "rng", "spec",
                                    "interpret"))
def proximity_lp_counts_grid(pos, lp, sender_mask, n_lp: int, area: float,
                             rng: float, spec: GridSpec,
                             interpret: bool):
    """Grid-candidate twin of proximity_lp_counts — bit-identical counts.

    `spec` must satisfy the cell-list contract (cell side >= rng,
    capacity >= max cell occupancy); use neighbors.make_grid_spec.
    """
    n = pos.shape[0]
    cand, _ = candidate_table(pos, spec)  # (N, 9 * capacity)
    valid = (cand >= 0) & (cand != jnp.arange(n, dtype=jnp.int32)[:, None])
    j = jnp.clip(cand, 0, n - 1)
    cx, cy = pos[j, 0], pos[j, 1]  # (N, C)
    clp = lp[j].astype(jnp.float32)

    bi, bc = min(BI, n), min(BC, cand.shape[1])
    pad_n = -n % bi
    pad_c = -cand.shape[1] % bc
    pad2 = lambda a, v: jnp.pad(a, ((0, pad_n), (0, pad_c)),
                                constant_values=v)
    cx, cy, clp = pad2(cx, 0.0), pad2(cy, 0.0), pad2(clp, 0.0)
    valid = pad2(valid.astype(jnp.float32), 0.0)
    px = jnp.pad(pos[:, 0:1], ((0, pad_n), (0, 0)))
    py = jnp.pad(pos[:, 1:2], ((0, pad_n), (0, 0)))
    sender = jnp.pad(sender_mask.astype(jnp.float32)[:, None],
                     ((0, pad_n), (0, 0)))
    np_, cp = n + pad_n, cand.shape[1] + pad_c
    lp_pad = max(n_lp, 8)

    out = pl.pallas_call(
        functools.partial(_kernel, area=float(area), rng2=float(rng) ** 2,
                          n_lp_pad=lp_pad),
        grid=(np_ // bi, cp // bc),
        in_specs=[
            pl.BlockSpec((bi, 1), lambda i, c: (i, 0)),
            pl.BlockSpec((bi, 1), lambda i, c: (i, 0)),
            pl.BlockSpec((bi, 1), lambda i, c: (i, 0)),
            pl.BlockSpec((bi, bc), lambda i, c: (i, c)),
            pl.BlockSpec((bi, bc), lambda i, c: (i, c)),
            pl.BlockSpec((bi, bc), lambda i, c: (i, c)),
            pl.BlockSpec((bi, bc), lambda i, c: (i, c)),
        ],
        out_specs=pl.BlockSpec((bi, lp_pad), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, lp_pad), jnp.float32),
        interpret=interpret,
    )(px, py, sender, cx, cy, clp, valid)
    return out[:n, :n_lp].astype(jnp.int32)
