"""Experiment 2 (paper Fig. 6): ΔLCR vs. Migration Ratio across #LPs.

Paper claim: with few LPs the self-clustering gains are large; splitting
the same model over more LPs lowers the achievable ΔLCR but stays > 0.
ΔLCR is a *paired* per-seed difference (ON and OFF run the same seeds),
so its ci95 excludes between-seed variance.
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmarks.common import (default_replicas, engine_cfg,  # noqa: E402
                               fmt_stat, paired_stats, run_cfg, write_csv)


def main(scale: str = "quick", replicas=None):
    n_rep = default_replicas(scale, replicas)
    lps = [2, 4, 8, 16, 32, 50]
    rows = []
    for n_lp in lps:
        on = run_cfg(engine_cfg(scale, n_lp=n_lp, speed=11.0, mf=1.2),
                     replicas=n_rep)
        off = run_cfg(engine_cfg(scale, n_lp=n_lp, speed=11.0, gaia=False),
                      replicas=n_rep)
        dlcr = paired_stats(on["reps"], off["reps"],
                            lambda a, b: a["mean_lcr"] - b["mean_lcr"])
        rows.append((n_lp, round(off["mean_lcr"], 4),
                     round(on["mean_lcr"], 4), round(dlcr["mean"], 4),
                     round(dlcr["ci95"], 4), n_rep,
                     round(on["migration_ratio"], 2)))
        print(f"[exp2] LPs={n_lp:<3} LCR {off['mean_lcr']:.3f} -> "
              f"{on['mean_lcr']:.3f} (dLCR {fmt_stat(dlcr)}, "
              f"MR {on['migration_ratio']:.1f})")
    path = write_csv("exp2.csv",
                     "n_lp,lcr_off,lcr_on,dlcr,dlcr_ci95,n,mr", rows)

    d = {r[0]: r[3] for r in rows}
    assert d[2] > 0.2 and d[4] > 0.2, f"few-LP gains too small: {d}"
    assert d[2] > d[32], "dLCR should shrink with more LPs"
    assert all(v > 0 for v in d.values()), f"dLCR must stay positive: {d}"
    print(f"[exp2] OK (n={n_rep}) -> {path}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[exp2] compile cache: {use_compile_cache()}")
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scale", nargs="?", default="quick",
                    choices=["quick", "mid", "paper"])
    ap.add_argument("--replicas", type=int, default=None)
    a = ap.parse_args()
    main(a.scale, a.replicas)
