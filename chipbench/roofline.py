"""Operations and bytes that a step's proximity phase requires, from the
configuration alone (never from a backend's shapes), so that every
implementation of the phase is held to the same count.

Per step, pi * N SEs send. A cell list with cells of side r, the
interaction range, finds every partner of a sender in the 3 x 3 block
of cells around it: 9 r^2 rho candidates at density rho = N / area^2.
Each candidate costs 12 operations: two differences, two absolute
values, two torus wraps (a subtraction and a minimum each), two
squares, one sum and one comparison; the per-LP count is an increment.

The phase must read each SE's position (2 x f32), LP (i32) and sender
flag (1 byte) once, and write the (N, L) i32 count matrix once.
"""
from __future__ import annotations

OPS_PER_CANDIDATE = 12


def proximity_work(engine: dict) -> tuple:
    """(operations, bytes) of one step's proximity phase."""
    m = engine["abm"]
    n, L = float(m["n_se"]), float(m["n_lp"])
    rho = n / float(m["area"]) ** 2
    r = float(m["interaction_range"])
    senders = float(m["p_interact"]) * n
    ops = senders * 9.0 * r * r * rho * OPS_PER_CANDIDATE
    nbytes = n * (8 + 4 + 1) + n * L * 4
    return ops, nbytes


def min_seconds(engine: dict, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound binds)."""
    ops, nbytes = proximity_work(engine)
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
