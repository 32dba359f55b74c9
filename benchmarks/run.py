"""Benchmark harness entrypoint: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale quick|mid|paper]
                                            [--only exp1,exp2,...]
                                            [--replicas R]
                                            [--trace [N_STEPS]]

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
    exp10     beyond-paper: telemetry overhead + step-phase trace
              export (BENCH_obs)
    tables23  Tables 2-3 + Figs. 8-9 — ΔWCT via the calibrated cost model
    gaiamoe   beyond-paper: adaptive MoE expert placement traffic
    roofline  assemble the §Roofline table from results/dryrun

`--trace` skips the benchmark sweep and exports step-phase trace
timelines instead (repro.obs.trace): one Chrome-trace/Perfetto JSON per
execution layer — results/trace_oracle.json and, on a >= 2-device
topology (forced automatically on CPU), results/trace_lp_device.json —
openable directly at https://ui.perfetto.dev or chrome://tracing. The
optional argument is the number of steps to trace (default 8).

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
import os
import sys
import time
import traceback


def trace_main(n_steps: int) -> int:
    """Export step-phase Perfetto timelines for both execution layers
    (the --trace mode). Must run before any bench import pulls in jax:
    the sharded trace needs a multi-device topology, which on CPU is an
    env var that only counts before the first jax import."""
    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    import dataclasses

    import jax

    from benchmarks.common import engine_cfg
    from repro.compile_cache import use_compile_cache
    from repro.obs import trace_run

    print(f"[trace] compile cache: {use_compile_cache()}")

    results = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "results")
    os.makedirs(results, exist_ok=True)
    base = dataclasses.replace(engine_cfg("quick"), timesteps=n_steps)
    layers = [("oracle", base)]
    n_dev = jax.device_count()
    if n_dev >= 2:
        layers.append(("lp_device", dataclasses.replace(
            base, sharding="lp_device", n_devices=min(n_dev, 4))))
    else:
        print("[trace] single-device topology: skipping the lp_device "
              "timeline")
    for name, cfg in layers:
        rec = trace_run(cfg, seed=0)
        path = rec.save(os.path.join(results, f"trace_{name}.json"))
        phases = rec.phase_summary()
        total = sum(st["total"] for st in phases.values())
        print(f"[trace] {name}: {n_steps} steps, "
              f"{len(phases)} phases, {total:.3f}s total -> {path}")
    print("[trace] open at https://ui.perfetto.dev or chrome://tracing")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick",
                    choices=["quick", "mid", "paper"])
    ap.add_argument("--only", default="")
    ap.add_argument("--replicas", type=int, default=None,
                    help="replica count for the statistical experiments "
                         "(default: 5 quick, 10 mid/paper)")
    ap.add_argument("--trace", nargs="?", type=int, const=8, default=None,
                    metavar="N_STEPS",
                    help="export step-phase Perfetto timelines instead of "
                         "running benchmarks (default 8 steps)")
    args = ap.parse_args()

    if args.trace is not None:
        return trace_main(args.trace)

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
