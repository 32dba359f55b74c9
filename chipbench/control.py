#!/usr/bin/env python3
"""The control of `correct`, at a cell's own size: the plain reference,
computing positions in bfloat16 (the precision below the configuration's
float32), put in the program's place and judged by the same comparison.
Each seed must come out not correct; the compared numbers it reads set
the upper end of each limit (PERF.md).

    python chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

Runs every seed in this one process and prints one JSON line per seed:
{"seed", "correct", "checks"}. The benchmark's own runs never run it.
"""
import argparse
import contextlib
import io
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax.numpy as jnp
    from chipbench import harness
    from chipbench.sut import ReferenceSUT

    def control(cfg, world, seed):
        return ReferenceSUT(cfg, world, seed, jnp.bfloat16)

    for seed in args.seeds:
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = harness.run(ROOT, args.workload, seed, args.seconds, False,
                             time.perf_counter(), sut_factory=control)
        if rc != 0:
            return rc
        res = json.loads(out.getvalue().strip().splitlines()[-1])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
