"""Experiment 4 (beyond-paper): proximity-backend scaling sweep.

The paper's hot spot is O(N^2) proximity matching; this sweep measures
one `interaction_counts` evaluation per backend across N (paper
defaults: area 10000, range 250, 4 LPs, pi 0.2) and records the results
in BENCH_proximity.json at the repo root.

Backends:
  dense        the O(N^2) oracle; row-chunked above `DENSE_CHUNK_ABOVE`
               SEs (same flop count, O(chunk*N) memory — the full pair
               matrix would not fit at 50k+)
  grid         cell-list neighbor search, O(N*k)
  pallas[...]  the TPU kernels; interpret mode on CPU executes the
               kernel body per tile in Python, so they are only timed at
               small N (they measure kernel *correctness* on CPU,
               kernel *speed* on TPU — see DESIGN.md §Adaptations)

Acceptance gate (tentpole): grid >= 5x faster than dense at N = 50k.

    PYTHONPATH=src python benchmarks/exp4_scaling.py [quick|full|scale]

quick: dense up to 50k, grid up to 100k, no pallas (a few minutes on one
CPU core). full: adds 100k dense and small-N pallas backends. scale:
quick plus the million-SE tier — grid-only cells at SCALE_NS, run at the
paper's *constant* density (the fixed-area sweep above densifies with N,
which is a different experiment), two decades past the old 50k ceiling.
Scale cells run the CSR candidate path under a hard memory budget and
record the grid_overflow flag so the curve is exact-or-loud.
"""
from __future__ import annotations

import json
import math
import os
import time

import jax

from repro.core.abm import ABMConfig, interaction_counts, \
    interaction_counts_overflow
from repro.core.engine import clear_compiled_caches
from repro.core.neighbors import dense_lp_counts_chunked
from repro.core.stats import replica_stats

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_proximity.json")

NS = (1_000, 10_000, 50_000, 100_000)
#: million-SE tier: two decades past the 50k gate, constant density
SCALE_NS = (500_000, 1_000_000, 5_000_000)
#: paper density 1e-4 SE/unit^2 (10k SEs on the 10_000^2 torus):
#: area(n) = sqrt(n / density) = 100 * sqrt(n)
SCALE_DENSITY = 1e-4
SCALE_BUDGET_MB = 512  # hard candidate-memory budget for scale cells
DENSE_CHUNK_ABOVE = 4096  # row-chunk the dense sweep past this N
PAPER = dict(n_lp=4, area=10_000.0, speed=11.0, interaction_range=250.0,
             p_interact=0.2)


def _inputs(n, seed=0, area=None):
    k = jax.random.key(seed)
    pos = jax.random.uniform(jax.random.fold_in(k, 0), (n, 2),
                             maxval=area or PAPER["area"])
    lp = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0,
                            PAPER["n_lp"])
    sender = jax.random.bernoulli(jax.random.fold_in(k, 2),
                                  PAPER["p_interact"], (n,))
    return pos, lp, sender


def _bench(fn, args, reps):
    fn(*args)  # compile + warm caches
    times = []
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        times.append(time.time() - t0)
    return times


def measure(n: int, backend: str, reps: int) -> dict:
    cfg = ABMConfig(n_se=n, proximity_backend=backend, **PAPER)
    args = _inputs(n)
    # arrays are jit *arguments*, never closed over: a closure would bake
    # them into the HLO as constants and invite constant folding, timing
    # dispatch overhead instead of the sweep
    if backend == "dense" and n > DENSE_CHUNK_ABOVE:
        fn = jax.jit(lambda p, l, s: dense_lp_counts_chunked(
            p, l, s, cfg.n_lp, cfg.area, cfg.interaction_range))
        note = "row-chunked"
    else:
        fn = jax.jit(lambda p, l, s: interaction_counts(p, l, s, cfg))
        note = ""
    times = _bench(fn, args, reps)
    stats = replica_stats(times)
    mean_s = stats["mean"]
    row = {"n": n, "backend": backend, "mean_s": round(mean_s, 4),
           "time_s": {k: round(v, 4) for k, v in stats.items()},
           "reps": reps, "pairs_per_s": round(n * n / mean_s)}
    if note:
        row["note"] = note
    spec = cfg.grid_spec()
    if backend in ("grid", "pallas_grid") and spec is not None:
        row["grid"] = {"ncell": spec.ncell, "capacity": spec.capacity}
    return row


def measure_scale(n: int, reps: int) -> dict:
    """One constant-density grid cell of the million-SE tier: CSR
    candidate path under `SCALE_BUDGET_MB`, overflow flag recorded (the
    curve is only meaningful where it is exact)."""
    area = 100.0 * math.sqrt(n)  # n / area^2 == SCALE_DENSITY
    cfg = ABMConfig(n_se=n, proximity_backend="grid",
                    mem_budget_mb=SCALE_BUDGET_MB,
                    **dict(PAPER, area=area))
    args = _inputs(n, area=area)
    fn = jax.jit(lambda p, l, s: interaction_counts(p, l, s, cfg))
    times = _bench(fn, args, reps)
    stats = replica_stats(times)
    mean_s = stats["mean"]
    _, ovf = interaction_counts_overflow(*args, cfg)
    spec = cfg.grid_spec()
    return {"n": n, "backend": "grid", "mean_s": round(mean_s, 4),
            "time_s": {k: round(v, 4) for k, v in stats.items()},
            "reps": reps, "pairs_per_s": round(n * n / mean_s),
            "area": round(area, 1), "density": SCALE_DENSITY,
            "mem_budget_mb": SCALE_BUDGET_MB,
            "grid_overflow": bool(ovf),
            "grid": {"ncell": spec.ncell, "capacity": spec.capacity}}


def main(scale: str = "quick"):
    # reps >= 3 everywhere: BENCH time_s entries must carry a real
    # ci95 (the n >= 3 schema requirement), dense@50k included
    plan = []  # (n, backend, reps)
    for n in NS:
        if n < 100_000 or scale == "full":
            plan.append((n, "dense", 3))
        plan.append((n, "grid", 5 if n <= 10_000 else 3))
    if scale == "full":
        plan += [(1_000, "pallas", 1), (1_000, "pallas_grid", 1)]

    rows = []
    for n, backend, reps in plan:
        row = measure(n, backend, reps)
        rows.append(row)
        print(f"[exp4] N={n:<7} {backend:<12} {row['mean_s']:.4f}s "
              f"({row['pairs_per_s']:.3g} pair/s)")

    scale_rows = []
    if scale == "scale":
        for n in SCALE_NS:
            # drop every compiled program from the previous cell: the
            # sweep's peak RSS must be one cell's, not the sum of all
            clear_compiled_caches()
            jax.clear_caches()
            row = measure_scale(n, reps=2 if n < 5_000_000 else 1)
            scale_rows.append(row)
            print(f"[exp4] N={n:<9} grid(scale)  {row['mean_s']:.4f}s "
                  f"({row['pairs_per_s']:.3g} pair/s, "
                  f"overflow={row['grid_overflow']})")
        assert not any(r["grid_overflow"] for r in scale_rows), \
            "scale tier overflowed its budgeted capacity (curve not exact)"

    by = {(r["n"], r["backend"]): r["mean_s"] for r in rows}
    speedups = {str(n): round(by[(n, "dense")] / by[(n, "grid")], 2)
                for n in NS if (n, "dense") in by and (n, "grid") in by}
    result = {
        "experiment": "exp4_scaling",
        "config": dict(PAPER, dense_chunk_above=DENSE_CHUNK_ABOVE,
                       scale=scale),
        "device": str(jax.devices()[0]),
        "results": rows,
        "grid_speedup_over_dense": speedups,
    }
    if scale_rows:
        result["scale_tier"] = {
            "density_se_per_unit2": SCALE_DENSITY,
            "mem_budget_mb": SCALE_BUDGET_MB,
            "results": scale_rows,
        }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    s50 = speedups.get("50000")
    assert s50 is not None and s50 >= 5.0, \
        f"grid speedup at 50k below the 5x gate: {s50}"
    print(f"[exp4] OK (50k speedup {s50}x) -> {OUT}")
    return result


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[exp4] compile cache: {use_compile_cache()}")
    import sys
    main(sys.argv[1] if len(sys.argv) > 1 else "quick")
