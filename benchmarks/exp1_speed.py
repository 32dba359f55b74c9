"""Experiment 1 (paper Fig. 5): LCR and migrations vs. node speed x MF.

Paper claim: at low speed, few migrations push LCR from the static 25%
(4 LPs) to ~90%; higher speed needs ever more migrations for the same
clustering level. Each cell runs `--replicas` seeds in one batched pass
(engine.run_batch) and reports mean/std/ci95/n; the trend assertions
test the replica means.
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmarks.common import (default_replicas, engine_cfg,  # noqa: E402
                               fmt_stat, run_cfg, write_csv)


def main(scale: str = "quick", replicas=None):
    n_rep = default_replicas(scale, replicas)
    speeds = [1, 5, 11, 19, 29]
    mfs = [1.1, 1.5, 3.0, 19.0]
    rows = []
    for speed in speeds:
        for mf in mfs:
            c = run_cfg(engine_cfg(scale, speed=speed, mf=mf),
                        replicas=n_rep)
            lcr, mig = c["stats"]["mean_lcr"], c["stats"]["migrations"]
            rows.append((speed, mf, round(lcr["mean"], 4),
                         round(lcr["std"], 4), round(lcr["ci95"], 4),
                         round(mig["mean"], 1), round(mig["ci95"], 1),
                         n_rep, round(c["migration_ratio"], 2),
                         round(c["wall_s"], 1)))
            print(f"[exp1] speed={speed:<3} MF={mf:<5} "
                  f"LCR={fmt_stat(lcr)} migs={fmt_stat(mig, 0)}")
    path = write_csv("exp1.csv",
                     "speed,mf,mean_lcr,lcr_std,lcr_ci95,migrations,"
                     "migrations_ci95,n,mr,wall_s", rows)

    # paper-claim checks (trends, on replica means)
    by = {(r[0], r[1]): r for r in rows}
    slow_aggr = by[(1, 1.1)]
    slow_off = by[(1, 19.0)]
    fast_aggr = by[(29, 1.1)]
    assert slow_aggr[2] > 0.55, f"low-speed clustering too weak: {slow_aggr}"
    assert slow_aggr[2] > slow_off[2] + 0.2, "MF sweep has no effect"
    assert fast_aggr[5] > slow_aggr[5], "fast nodes should need more migs"
    print(f"[exp1] OK (n={n_rep}) -> {path}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[exp1] compile cache: {use_compile_cache()}")
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scale", nargs="?", default="quick",
                    choices=["quick", "mid", "paper"])
    ap.add_argument("--replicas", type=int, default=None)
    a = ap.parse_args()
    main(a.scale, a.replicas)
