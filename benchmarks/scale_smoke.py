"""Million-SE memory smoke (nightly): bounded memory at N = 10^6.

The scale tier's claim is not "it is fast" but "it fits and it is
exact": a 1M-SE hotspot workload (constant paper density, clustered —
the layout that used to blow up the dense candidate matrix) must run
through the real engine window with

  * peak RSS under a hard ceiling — the CSR candidate path plus the
    `mem_budget_mb` knob bound every transient, so memory is O(N) with
    a small constant, never O(N * 9 * capacity) materialized at once;
  * `grid_overflow == 0` — the budget did not buy memory by silently
    undercounting neighbors (the exact-or-loud contract).

Writes BENCH_scale.json with the two tracked metrics
(`rss_per_se_bytes`, `grid_overflow_steps`) plus timing context.
benchmarks/compare.py gates both against BENCH_baseline/: the zero
overflow baseline makes any tripped step a failure, and bytes/SE moving
past its tolerance means the memory model regressed.

    PYTHONPATH=src python benchmarks/scale_smoke.py [--n N] [--steps S]

Defaults are the CI nightly configuration (~3 engine steps at 1M SEs,
a few minutes on one CPU core). `--n` exists for quicker local runs;
BENCH_scale.json records the n it was produced with.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import resource
import sys
import time

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
import jax  # noqa: E402

from repro.core.abm import ABMConfig  # noqa: E402
from repro.core.engine import (EngineConfig, clear_compiled_caches,  # noqa: E402
                               init_engine, run_window)
from repro.core.heuristics import HeuristicConfig  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_scale.json")

N_SE = 1_000_000
STEPS = 3
MEM_BUDGET_MB = 512  # hard candidate/halo memory budget (EngineConfig)
#: peak-RSS gate. Measured ~1.25 GB on the reference box (jax runtime +
#: XLA compile workspace + one budgeted window at 1M); the ceiling is
#: ~2.5x that — a regression back toward a dense candidate matrix
#: (~ (N, 9*cap) i32 = GBs at 1M before the first query even runs)
#: clears it immediately, while allocator/runner noise does not.
RSS_CEILING_MB = 3072


def scale_cfg(n: int) -> EngineConfig:
    """Constant paper density (1e-4 SE/unit^2), hotspot mobility: the
    clustered layout is the adversarial one for per-cell capacity, and
    the mobility-aware auto capacity + budget clamp must hold it."""
    area = 100.0 * math.sqrt(n)
    abm = ABMConfig(n_se=n, n_lp=4, area=area, speed=11.0,
                    interaction_range=250.0, p_interact=0.2,
                    mobility="hotspot", n_groups=max(4, n // 4000),
                    group_radius=area * 0.08)
    return EngineConfig(abm=abm, heuristic=HeuristicConfig(mf=1.2, mt=5),
                        gaia_on=False, timesteps=STEPS,
                        mem_budget_mb=MEM_BUDGET_MB)


def peak_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=N_SE)
    ap.add_argument("--steps", type=int, default=STEPS)
    args = ap.parse_args(argv)

    cfg = scale_cfg(args.n)
    spec = cfg.abm.grid_spec()
    print(f"[scale] N={args.n} area={cfg.abm.area:.0f} "
          f"grid={spec.ncell}x{spec.ncell} capacity={spec.capacity} "
          f"budget={MEM_BUDGET_MB}MB")

    clear_compiled_caches()
    t0 = time.time()
    st = init_engine(jax.random.key(0), cfg)
    jax.block_until_ready(st["pos"])
    t_init = time.time() - t0

    t0 = time.time()
    st, counters = run_window(st, cfg, args.steps)
    t_window = time.time() - t0

    rss = peak_rss_bytes()
    result = {
        "experiment": "scale_smoke",
        "n_se": args.n,
        "steps": args.steps,
        "mem_budget_mb": MEM_BUDGET_MB,
        "grid": {"ncell": spec.ncell, "capacity": spec.capacity},
        "device": str(jax.devices()[0]),
        "rss_peak_mb": round(rss / 2**20, 1),
        "rss_per_se_bytes": round(rss / args.n, 1),
        "grid_overflow_steps": counters["grid_overflow"],
        "init_s": round(t_init, 2),
        "window_s": round(t_window, 2),
        "step_s": round(t_window / args.steps, 2),
        "migrations": counters["migrations"],
        "mean_lcr": round(counters["mean_lcr"], 4),
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(f"[scale] {args.steps} steps in {t_window:.1f}s "
          f"({result['step_s']}s/step), peak RSS {result['rss_peak_mb']}MB "
          f"({result['rss_per_se_bytes']} B/SE), "
          f"overflow={result['grid_overflow_steps']} -> {OUT}")

    assert result["grid_overflow_steps"] == 0, \
        "grid overflow tripped: the budgeted capacity undercounted (loud)"
    if args.n >= N_SE:  # the ceiling is sized for the nightly config
        assert rss <= RSS_CEILING_MB * 2**20, \
            f"peak RSS {result['rss_peak_mb']}MB over the " \
            f"{RSS_CEILING_MB}MB ceiling"
    print("[scale] OK")
    return result


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[scale] compile cache: {use_compile_cache()}")
    main()
