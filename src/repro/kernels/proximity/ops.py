"""Public entry points for the proximity kernels.

proximity_lp_counts       dense-sweep kernel (O(N^2) pairs, MXU histogram)
proximity_lp_counts_grid  cell-list kernel (O(N*C) candidate pairs)
proximity_lp_counts_ref   pure-jnp oracle

The two Pallas entry points pick their lowering here, in one place: the
compiled Mosaic kernel whenever the target is a TPU, the Pallas
interpreter only when lowering for the CPU. `jax.lax.platform_dependent`
makes that choice when the program is lowered, not when Python runs, so
a compile for a described TPU from a CPU host gets the Mosaic kernel
too. The kernel parity tests call the kernel modules with `interpret=True`.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.proximity import grid as _grid
from repro.kernels.proximity import proximity as _dense
from repro.kernels.proximity.ref import proximity_lp_counts_ref  # noqa: F401


def _lowered_for_target(kernel, args, **static):
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, interpret=True, **static),
        default=functools.partial(kernel, interpret=False, **static))


def proximity_lp_counts(pos, lp, sender_mask, n_lp: int, area: float,
                        rng: float):
    """Dense-sweep per-sender LP histogram (see proximity.py)."""
    return _lowered_for_target(
        _dense.proximity_lp_counts, (pos, lp, sender_mask),
        n_lp=n_lp, area=area, rng=rng)


def proximity_lp_counts_grid(pos, lp, sender_mask, n_lp: int, area: float,
                             rng: float, spec):
    """Cell-list twin of `proximity_lp_counts` (see grid.py)."""
    return _lowered_for_target(
        _grid.proximity_lp_counts_grid, (pos, lp, sender_mask),
        n_lp=n_lp, area=area, rng=rng, spec=spec)
