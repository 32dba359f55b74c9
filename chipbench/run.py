#!/usr/bin/env python3
"""Chip benchmark of the GAIA engine: one cell, one run, one process.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

`BENCHMARK.json` at the root of the checkout names the cell's
configuration (`chipbench/configs/<config>.json`) and traffic mix
(`chipbench/traffic/<mix>.json`). Set-up builds the engine from the
seed and runs the mix's round until a round compiles nothing, so that
every program the window uses is compiled or read from the cache; the
window then repeats the round for `--seconds`.
After the window the plain reference (chipbench/reference.py) replays
every operation from the same seed and the run is `correct` when every
compared number is within its limit.

`--trace 0` prints the cell's end-to-end metrics; `--trace 1` runs the
window under the profiler and prints the per-layer metrics, each read
by `chipbench/metrics/<name>.py`.

The last line of standard output is one JSON object; the last lines of
standard error give each compared number beside its limit. Without a
TPU, or with fewer chips than the cell asks for, the run exits 3 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout; the variable must be set before JAX is imported, and the
    # program takes the directory it names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    return harness.run(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
