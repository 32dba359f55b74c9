"""Benchmark harness entrypoint: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale quick|mid|paper]
                                            [--only exp1,exp2,...]
                                            [--replicas R]

Experiments (see DESIGN.md §Per-experiment index):
    exp1      Fig. 5  — LCR & migrations vs. speed x MF
    exp2      Fig. 6  — ΔLCR vs. #LPs
    exp3      Fig. 7  — ΔLCR vs. interaction range
    exp4      beyond-paper: proximity-backend scaling (BENCH_proximity)
    exp5      beyond-paper: LP-per-device sharded engine (BENCH_sharded)
    exp6      beyond-paper: mobility scenarios x environments
              (BENCH_scenarios)
    exp7      beyond-paper: partitioning backends vs adaptive GAIA
              (BENCH_partition)
    exp8      beyond-paper: batched-replica engine throughput
              (BENCH_replicas)
    exp9      beyond-paper: resident engine service — open-world churn
              throughput + request multiplexing (BENCH_service)
    exp10     beyond-paper: telemetry overhead (BENCH_obs)
    tables23  Tables 2-3 + Figs. 8-9 — ΔWCT via the calibrated cost model
    gaiamoe   beyond-paper: adaptive MoE expert placement traffic
    roofline  assemble the §Roofline table from results/dryrun

`--replicas` sets the replica count for the statistical experiments
(exp1/2/3/6/7, tables23 — and the batch size of exp8); the default is 5
in quick mode and 10 at mid/paper scale. Replicas run in one batched
device pass (engine.run_batch) and every reported metric carries
mean/std/ci95/n (see README §Benchmarks).

The dry-run campaign itself (benchmarks/dryrun_all.py) is run separately
(it spawns one 512-device subprocess per cell).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick",
                    choices=["quick", "mid", "paper"])
    ap.add_argument("--only", default="")
    ap.add_argument("--replicas", type=int, default=None,
                    help="replica count for the statistical experiments "
                         "(default: 5 quick, 10 mid/paper)")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    print(f"[run] compile cache: {use_compile_cache()}")
    from benchmarks import (exp1_speed, exp2_lps, exp3_range, exp4_scaling,
                            exp5_sharded, exp6_scenarios, exp7_partition,
                            exp8_replicas, exp9_service, exp10_obs, fleet,
                            tables23, gaia_moe_bench, roofline,
                            selftune_bench)
    # exp4..exp8 expose quick|full: paper-scale maps to their full sweep
    qf = "quick" if args.scale == "quick" else "full"
    rep = args.replicas
    benches = {
        "exp1": lambda: exp1_speed.main(args.scale, rep),
        "exp2": lambda: exp2_lps.main(args.scale, rep),
        "exp3": lambda: exp3_range.main(args.scale, rep),
        "exp4": lambda: exp4_scaling.main(qf),
        "exp5": lambda: exp5_sharded.main(qf),
        "exp6": lambda: exp6_scenarios.main(qf, rep),
        # the fleet runs exp6's matrix (plus the partitioner/device
        # axes) as subprocess cells and writes the same
        # BENCH_scenarios.json — so it replaces exp6 when selected and
        # is excluded from the run-everything default to avoid running
        # the sweep twice
        "fleet": lambda: fleet.main(qf, rep),
        "exp7": lambda: exp7_partition.main(qf, rep),
        "exp8": lambda: exp8_replicas.main(qf, rep),
        "exp9": lambda: exp9_service.main(qf),
        "exp10": lambda: exp10_obs.main(qf),
        "tables23": lambda: tables23.main(args.scale, rep),
        "gaiamoe": lambda: gaia_moe_bench.main(args.scale),
        "selftune": lambda: selftune_bench.main(args.scale),
        "roofline": lambda: roofline.main(),
    }
    only = [s for s in args.only.split(",") if s] or \
        [k for k in benches if k != "fleet"]
    failures = []
    for name in only:
        t0 = time.time()
        print(f"\n===== {name} ({args.scale}) =====", flush=True)
        try:
            benches[name]()
            print(f"===== {name}: PASS ({time.time()-t0:.0f}s) =====")
        except Exception:
            traceback.print_exc()
            failures.append(name)
            print(f"===== {name}: FAIL ({time.time()-t0:.0f}s) =====")
    if failures:
        print(f"\nFAILED: {failures}")
        return 1
    print("\nAll benchmarks passed.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
