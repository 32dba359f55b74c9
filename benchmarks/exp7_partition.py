"""Experiment 7 (beyond-paper): partitioning backends vs. adaptive GAIA.

The paper's headline claim — adaptive self-clustering beats static
partitioning — was never measured in this repo because the only static
baseline was the random round-robin map. This sweep runs the partition
registry (core/partition.py) against it on the non-uniform mobility
scenarios, in three modes per backend:

  static    the backend computes the initial map, nothing adapts
  periodic  the backend recomputes the global map every R steps
            (EngineConfig.repartition_every; deltas ride the migration
            machinery and are priced as migrations)
  gaia      GAIA ON on top of a static init (random = the paper's
            setting; kmeans = adaptive refinement of an informed start)

Each (scenario, backend, mode) cell runs `--replicas` seeds in one
batched engine pass (engine.run_batch) and serves every environment:
counters are environment-independent, only the pricing changes
(wct_env on the shm/lan/wan2/hetero presets). Metrics and gate ratios
are mean/std/ci95/n stats dicts; the ratios are *paired* per seed (all
cells run the same seed vector).

Acceptance gate (lan pricing, replica means), per non-uniform scenario:
  (a) at least one informed static/periodic backend must beat the
      random static map on TEC — the baselines are real;
  (b) the best GAIA row must beat or match (<= 2% above) the best
      *static* row — the paper's claim, measured against baselines that
      actually try. Periodic global repartitioning is deliberately NOT
      in (b)'s floor: recomputing the map every R steps is itself a
      (coarse-grained, centralized) adaptive scheme, the alternative
      GAIA should be compared to, not a static bar it must clear; the
      gaia_vs_best_anything ratio is still reported for the record.

    PYTHONPATH=src python benchmarks/exp7_partition.py [quick|full]
                                                       [--replicas R]

quick: N=1000, 300 steps (CI-sized), 5 replicas default. full:
N=10000, 1200 steps, 10 replicas default. Writes BENCH_partition.json
at the repo root (CI artifact; tracked by benchmarks/compare.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import default_replicas  # noqa: E402
from repro.core import costmodel as cm  # noqa: E402
from repro.core.abm import ABMConfig  # noqa: E402
from repro.core.engine import EngineConfig, run_batch  # noqa: E402
from repro.core.heuristics import HeuristicConfig  # noqa: E402
from repro.core.stats import replica_stats, summarize  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_partition.json")

SCALES = {
    # n_se, timesteps, area: paper density 1e-4 SE/unit^2, like common.py
    "quick": dict(n_se=1_000, timesteps=300, area=3162.0, repart_every=50),
    "full": dict(n_se=10_000, timesteps=1200, area=10_000.0,
                 repart_every=100),
}
SCENARIOS = ("hotspot", "group")  # the gated non-uniform workloads
BACKENDS = ("random", "stripe", "kmeans", "bestresponse")
PERIODIC_BACKENDS = ("stripe", "kmeans", "bestresponse")
GAIA_INITS = ("random", "kmeans")  # paper setting / informed start
ENVS = ("shm", "lan", "wan2", "hetero")
GATE_ENV = "lan"
GAIA_MATCH_TOL = 0.02  # gaia row may be at most 2% above the best static
N_LP = 4
INTERACTION_BYTES = 100
MIGRATION_BYTES = 256


def exp_cfg(scale: str, scenario: str, backend: str, *, gaia: bool,
            repart: int = 0) -> EngineConfig:
    s = SCALES[scale]
    f = s["area"] / 10_000.0  # speed scaling, as in benchmarks/common.py
    return EngineConfig(
        abm=ABMConfig(n_se=s["n_se"], n_lp=N_LP, area=s["area"],
                      speed=11.0 * f, interaction_range=250.0,
                      p_interact=0.2, mobility=scenario, n_groups=8,
                      group_radius=250.0, partitioner=backend),
        heuristic=HeuristicConfig(mf=1.2, mt=10),
        gaia_on=gaia, repartition_every=repart, timesteps=s["timesteps"])


def one_run(cfg: EngineConfig, envs: dict, timesteps: int, seeds) -> dict:
    """One batched cell: per-replica counters -> stats dicts + raw
    per-replica TEC lists (under "_tec_reps", stripped before the JSON
    dump — the gate pairs them across cells by seed)."""
    t0 = time.time()
    _, _, reps = run_batch(cfg, seeds)
    st = summarize(reps, ndigits=4)
    tec_reps = {kind: [cm.wct_env(
        r, cm.DISTRIBUTED, env, timesteps,
        interaction_bytes=INTERACTION_BYTES,
        migration_bytes=MIGRATION_BYTES)["TEC"] for r in reps]
        for kind, env in envs.items()}
    return {
        "lcr": st["mean_lcr"],
        "migrations": st["migrations"],
        "repartitions": st.get("repartitions",
                               {"mean": 0.0, "std": 0.0, "ci95": 0.0,
                                "n": len(seeds)}),
        "grid_overflow": sum(r["grid_overflow"] for r in reps),
        "wall_s": round(time.time() - t0, 1),
        "tec": {kind: {k: round(v, 3)
                       for k, v in replica_stats(ts).items()}
                for kind, ts in tec_reps.items()},
        "_tec_reps": tec_reps,
    }


def main(scale: str = "quick", replicas=None):
    s = SCALES[scale]
    n_rep = default_replicas(scale, replicas)
    seeds = list(range(n_rep))
    envs = {kind: cm.make_env(kind, N_LP) for kind in ENVS}
    results = {}
    for scen in SCENARIOS:
        rows = {}
        for backend in BACKENDS:
            cfg = exp_cfg(scale, scen, backend, gaia=False)
            rows[f"{backend}/static"] = one_run(cfg, envs, s["timesteps"],
                                                seeds)
        for backend in PERIODIC_BACKENDS:
            cfg = exp_cfg(scale, scen, backend, gaia=False,
                          repart=s["repart_every"])
            rows[f"{backend}/periodic"] = one_run(cfg, envs,
                                                  s["timesteps"], seeds)
        for backend in GAIA_INITS:
            cfg = exp_cfg(scale, scen, backend, gaia=True)
            rows[f"{backend}/gaia"] = one_run(cfg, envs, s["timesteps"],
                                              seeds)
        results[scen] = rows
        for name, row in rows.items():
            print(f"[exp7] {scen:8s} {name:22s} "
                  f"lcr {row['lcr']['mean']:.3f}  "
                  f"TEC({GATE_ENV}) {row['tec'][GATE_ENV]['mean']:9.3f}"
                  f"±{row['tec'][GATE_ENV]['ci95']:.3f}  "
                  f"migs {row['migrations']['mean']:7.0f} "
                  f"(reparts {row['repartitions']['mean']:.0f}, n={n_rep})")

    # -- gate: measured on the lan environment, ratios paired per seed --
    gate = {"static_gain_by_scenario": {}, "gaia_vs_best_static": {},
            "gaia_vs_best_anything": {}, "static_winner": {},
            "gaia_winner": {}}
    ok_a, ok_b = [], []
    for scen, rows in results.items():
        tec = {name: row["tec"][GATE_ENV]["mean"]
               for name, row in rows.items()}
        reps = {name: row["_tec_reps"][GATE_ENV]
                for name, row in rows.items()}
        rand = tec["random/static"]
        informed = {k: v for k, v in tec.items()
                    if k.endswith(("/static", "/periodic"))
                    and k != "random/static"}
        static = {k: v for k, v in tec.items() if k.endswith("/static")}
        adaptive = {k: v for k, v in tec.items() if k.endswith("/gaia")}
        # winners chosen on replica-mean TEC; ratios then paired per seed
        best_informed = min(informed, key=informed.get)
        best_static = min(static, key=static.get)
        best_gaia = min(adaptive, key=adaptive.get)
        gate["static_gain_by_scenario"][scen] = {
            k: round(v, 4) for k, v in replica_stats(
                [(r - i) / r for r, i in
                 zip(reps["random/static"], reps[best_informed])]).items()}
        gate["gaia_vs_best_static"][scen] = {
            k: round(v, 4) for k, v in replica_stats(
                [g / st for g, st in
                 zip(reps[best_gaia], reps[best_static])]).items()}
        gate["gaia_vs_best_anything"][scen] = {
            k: round(v, 4) for k, v in replica_stats(
                [g / i for g, i in
                 zip(reps[best_gaia], reps[best_informed])]).items()}
        gate["static_winner"][scen] = best_informed
        gate["gaia_winner"][scen] = best_gaia
        ok_a.append(informed[best_informed] < rand)
        ok_b.append(adaptive[best_gaia]
                    <= min(static.values()) * (1.0 + GAIA_MATCH_TOL))
        print(f"[exp7] {scen}: best baseline {best_informed} "
              f"({gate['static_gain_by_scenario'][scen]['mean']:+.1%} vs "
              f"random), best GAIA {best_gaia} "
              f"(x{gate['gaia_vs_best_static'][scen]['mean']:.3f} of best "
              f"static, x{gate['gaia_vs_best_anything'][scen]['mean']:.3f}"
              f" of best baseline)")

    for rows in results.values():  # raw pairing lists: not for the JSON
        for row in rows.values():
            del row["_tec_reps"]
    result = {
        "experiment": "exp7_partition",
        "config": dict(s, n_lp=N_LP, scale=scale, replicas=n_rep,
                       interaction_bytes=INTERACTION_BYTES,
                       migration_bytes=MIGRATION_BYTES, gate_env=GATE_ENV,
                       gaia_match_tol=GAIA_MATCH_TOL),
        "results": results,
        "gate": gate,
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)

    for scen, rows in results.items():
        for name, row in rows.items():
            assert row["grid_overflow"] == 0.0, \
                f"grid overflow on {scen}/{name}"
    assert all(ok_a), \
        f"(a) no informed backend beat random/static on TEC({GATE_ENV}): " \
        f"{gate['static_gain_by_scenario']}"
    assert all(ok_b), \
        f"(b) GAIA failed to beat/match the best static backend on " \
        f"TEC({GATE_ENV}): {gate['gaia_vs_best_static']}"
    print(f"[exp7] OK (n={n_rep}) -> {OUT}")
    return result


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[exp7] compile cache: {use_compile_cache()}")
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scale", nargs="?", default="quick",
                    choices=["quick", "full"])
    ap.add_argument("--replicas", type=int, default=None)
    a = ap.parse_args()
    main(a.scale, a.replicas)
