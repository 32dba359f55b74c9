"""Experiment 3 (paper Fig. 7): ΔLCR vs. threshold interaction range.

Paper claim: tiny ranges make unstable micro-clusters (many migrations,
mediocre ΔLCR); mid ranges cluster best; very large ranges overlap
everyone's neighborhoods and clustering quality degrades again. ΔLCR is
paired per seed, as in exp2.
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmarks.common import (SCALES, default_replicas,  # noqa: E402
                               engine_cfg, fmt_stat, paired_stats, run_cfg,
                               write_csv)


def main(scale: str = "quick", replicas=None):
    n_rep = default_replicas(scale, replicas)
    # ranges scale with the area (the paper's 50..1600 on a 10k-side torus)
    side = SCALES[scale]["area"]
    fracs = [0.005, 0.01, 0.02, 0.04, 0.08, 0.16]
    rows = []
    for frac in fracs:
        rng = side * frac
        on = run_cfg(engine_cfg(scale, rng=rng, mf=1.2), replicas=n_rep)
        off = run_cfg(engine_cfg(scale, rng=rng, gaia=False),
                      replicas=n_rep)
        dlcr = paired_stats(on["reps"], off["reps"],
                            lambda a, b: a["mean_lcr"] - b["mean_lcr"])
        rows.append((round(rng, 1), round(dlcr["mean"], 4),
                     round(dlcr["ci95"], 4), n_rep,
                     round(on["migration_ratio"], 2)))
        print(f"[exp3] range={rng:7.1f} dLCR {fmt_stat(dlcr)} "
              f"MR {on['migration_ratio']:.1f}")
    path = write_csv("exp3.csv", "range,dlcr,dlcr_ci95,n,mr", rows)

    d = {r[0]: r[1] for r in rows}
    vals = [d[round(side * f, 1)] for f in fracs]
    mid = max(vals[1:4])
    assert mid > vals[-1], f"huge ranges should degrade clustering: {vals}"
    assert mid > 0.15, f"mid-range clustering too weak: {vals}"
    print(f"[exp3] OK (n={n_rep}) -> {path}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[exp3] compile cache: {use_compile_cache()}")
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scale", nargs="?", default="quick",
                    choices=["quick", "mid", "paper"])
    ap.add_argument("--replicas", type=int, default=None)
    a = ap.parse_args()
    main(a.scale, a.replicas)
