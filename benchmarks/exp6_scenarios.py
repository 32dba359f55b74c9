"""Experiment 6 (beyond-paper): scenario x environment sweep.

The paper claims self-clustering pays off across "various configurations
of the simulation model and the execution environment"; the earlier
experiments only exercise uniform RWP on homogeneous devices — the
friendliest case. This sweep runs the non-uniform mobility workloads
(hotspot attractors, RPGM-style groups, emergent flocking) plus the two
*workload families* beyond pure mobility — `trace` (hub-clustered
commuter traces replayed through the data pipeline) and `epidemic`
(SI/SIS diffusion whose epi_boost send weight follows the infection
wave, not the density map) — with GAIA on and off, prices each run on
every ExecutionEnvironment preset (shared-memory / LAN / two-site WAN /
heterogeneous speeds) with the per-LP-pair cost layer, and records
everything in BENCH_scenarios.json at the repo root (uploaded as a CI
artifact and tracked by the bench-regression gate,
benchmarks/compare.py).

Each (scenario, gaia) cell runs `--replicas` seeds in ONE batched
engine pass (engine.run_batch) and serves all environments: counters
are environment-independent; only the *pricing* changes (that is the
point of the §3 cost layer). Every reported metric is a
mean/std/ci95/n stats dict (src/repro/core/stats.py); TEC gains are
paired per seed (ON and OFF run the same seeds).

The per-scenario cell is exposed as `run_cell` so the fleet runner
(benchmarks/fleet.py) can execute the same cells in isolated
subprocesses — one per {scenario x partitioner x device-count} matrix
point — and merge the rows back into this file's output schema. Running
this module directly is the single-process equivalent of the fleet's
D=1 gate lane.

Acceptance gate: on the LAN environment GAIA must reduce mean TEC vs
static partitioning on >= 2 of the 3 non-uniform mobility scenarios,
and no replica may overflow the proximity grid (the clustered
auto-capacity must hold). The workload families' gains are reported in
the same stats schema and regression-tracked by compare.py.

    PYTHONPATH=src python benchmarks/exp6_scenarios.py [quick|full]
                                                       [--replicas R]

quick: N=1000, 300 steps (CI-sized), 5 replicas default. full:
N=10000, 1200 steps, 10 replicas default.
"""
from __future__ import annotations

import json
import os
import sys
import time

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks.common import default_replicas  # noqa: E402
from repro.core import costmodel as cm  # noqa: E402
from repro.core.abm import ABMConfig  # noqa: E402
from repro.core.engine import EngineConfig, run_batch  # noqa: E402
from repro.core.heuristics import HeuristicConfig  # noqa: E402
from repro.core.stats import replica_stats, summarize  # noqa: E402
from repro.data import pipeline as dpipe  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_scenarios.json")

SCALES = {
    # n_se, timesteps, area: paper density 1e-4 SE/unit^2, like common.py
    "quick": dict(n_se=1_000, timesteps=300, area=3162.0),
    "full": dict(n_se=10_000, timesteps=1200, area=10_000.0),
}
#: rwp = uniform reference row; hotspot/group/flock = non-uniform
#: mobility; trace/epidemic = the workload families beyond pure mobility
SCENARIOS = ("rwp", "hotspot", "group", "flock", "trace", "epidemic")
NEW_SCENARIOS = ("hotspot", "group", "flock")  # the >=2-wins gate set
WORKLOAD_SCENARIOS = ("trace", "epidemic")  # reported + tracked, not gated
ENVS = ("shm", "lan", "wan2", "hetero")
GATE_ENV = "lan"
N_LP = 4
INTERACTION_BYTES = 100
MIGRATION_BYTES = 256

#: SIS parameters of the epidemic cell: slow wave (beta), endemic
#: turnover (gamma) so the hot region keeps moving instead of
#: saturating, and a strong send-weight boost — the load follows the
#: wave, which is the property no pure-mobility scenario has
EPI = dict(workload="epidemic", epi_beta=0.05, epi_gamma=0.08,
           epi_seed_frac=0.05, epi_boost=5.0)


def ensure_trace(scale: str) -> str:
    """Register (idempotently) the deterministic commuter trace backing
    the `trace` scenario at this scale and return its registry name.

    Length is timesteps + 1 frames: step t replays frame t + 1, so the
    'exact' policy covers the full horizon with no loop seam (a seam
    jump would inflate the halo dilation radius for nothing). Any
    process that runs a trace cell — this module or a fleet child —
    calls this before building the engine."""
    s = SCALES[scale]
    name = f"exp6-{scale}"
    if name not in dpipe.trace_names():
        spec = dpipe.TraceSpec(
            n_se=s["n_se"], area=s["area"], timesteps=s["timesteps"] + 1,
            speed=11.0 * s["area"] / 10_000.0, n_hubs=8, seed=0)
        dpipe.register_trace(name, dpipe.synthetic_trace(spec))
    return name


def scenario_cfg(scale: str, scenario: str, gaia: bool,
                 partitioner: str = "random",
                 repartition_every: int = 0,
                 n_devices: int = 1) -> EngineConfig:
    s = SCALES[scale]
    f = s["area"] / 10_000.0  # speed scaling, as in benchmarks/common.py
    abm_kw = dict(n_se=s["n_se"], n_lp=N_LP, area=s["area"],
                  speed=11.0 * f, interaction_range=250.0,
                  p_interact=0.2, mobility=scenario, n_groups=8,
                  group_radius=250.0, partitioner=partitioner)
    if scenario == "trace":
        abm_kw.update(mobility="trace", trace_name=ensure_trace(scale),
                      trace_policy="exact")
    elif scenario == "epidemic":
        abm_kw.update(mobility="rwp", **EPI)
    eng_kw = dict(heuristic=HeuristicConfig(mf=1.2, mt=10),
                  gaia_on=gaia, timesteps=s["timesteps"],
                  repartition_every=repartition_every)
    if n_devices > 1:
        eng_kw.update(sharding="lp_device", n_devices=n_devices)
    return EngineConfig(abm=ABMConfig(**abm_kw), **eng_kw)


def density_stats(pos, cfg: EngineConfig) -> dict:
    """How non-uniform did the workload actually get? Peak cell
    occupancy over the uniform mean (1.0 = perfectly uniform), on one
    replica's final positions."""
    spec = cfg.abm.grid_spec()
    if spec is None:
        return {}
    pos = np.asarray(pos)
    cell = (np.floor(pos[:, 0] / spec.cell).astype(int)
            % spec.ncell) * spec.ncell + \
        (np.floor(pos[:, 1] / spec.cell).astype(int) % spec.ncell)
    occ = np.bincount(cell, minlength=spec.ncell ** 2)
    mean = cfg.abm.n_se / spec.ncell ** 2
    return {"peak_cell_over_uniform": round(float(occ.max() / mean), 2),
            "grid_capacity": spec.capacity}


def run_cell(scale: str, scenario: str, seeds,
             partitioner: str = "random",
             repartition_every: int = 0) -> dict:
    """One benchmark cell: the paired GAIA on/off batched runs for one
    scenario, priced on every environment. Returns the BENCH row dict.

    This is the unit the fleet runner forks into a subprocess; keeping
    it a pure function of (scale, scenario, seeds, partitioner) is what
    makes the fleet's merged output identical to a sequential run."""
    s = SCALES[scale]
    n_rep = len(seeds)
    row = {"scenario": scenario, "n": n_rep}
    if partitioner != "random" or repartition_every:
        row["partitioner"] = partitioner
        row["repartition_every"] = repartition_every
    reps_by_gaia = {}
    for gaia in (True, False):
        cfg = scenario_cfg(scale, scenario, gaia, partitioner,
                           repartition_every)
        t0 = time.time()
        states, _, reps = run_batch(cfg, seeds)
        reps_by_gaia[gaia] = reps
        tag = "on" if gaia else "off"
        row[f"wall_s_{tag}"] = round(time.time() - t0, 1)
        st = summarize(reps, ndigits=4)
        row[f"lcr_{tag}"] = st["mean_lcr"]
        row[f"grid_overflow_{tag}"] = sum(r["grid_overflow"] for r in reps)
        if gaia:
            row["migrations"] = st["migrations"]
            if scenario == "epidemic":
                row["infected"] = st.get("mean_infected", {})
            row.update(density_stats(states["pos"][0], cfg))
    row["tec"] = {}
    for kind in ENVS:
        env = cm.make_env(kind, N_LP)
        per_rep = {}
        for gaia in (True, False):
            per_rep["on" if gaia else "off"] = [
                cm.wct_env(r, cm.DISTRIBUTED, env, s["timesteps"],
                           interaction_bytes=INTERACTION_BYTES,
                           migration_bytes=MIGRATION_BYTES)["TEC"]
                for r in reps_by_gaia[gaia]]
        gain = replica_stats([(off - on) / off for on, off in
                              zip(per_rep["on"], per_rep["off"])])
        row["tec"][kind] = {
            "on": {k: round(v, 3) for k, v
                   in replica_stats(per_rep["on"]).items()},
            "off": {k: round(v, 3) for k, v
                    in replica_stats(per_rep["off"]).items()},
            "gain": {k: round(v, 4) for k, v in gain.items()},
        }
    return row


def print_row(row: dict) -> None:
    g = row["tec"][GATE_ENV]["gain"]
    print(f"[exp6] {row['scenario']:8s} "
          f"lcr {row['lcr_off']['mean']:.3f} -> "
          f"{row['lcr_on']['mean']:.3f}  peak-density "
          f"{row.get('peak_cell_over_uniform', '-')}x  "
          f"TEC({GATE_ENV}) gain {g['mean']:+.1%}±{g['ci95']:.1%} "
          f"(n={row['n']})")


def assemble(rows: list, scale: str, n_rep: int,
             fleet: dict | None = None) -> dict:
    """Fold per-scenario rows into the BENCH_scenarios.json document.
    `rows` must hold the D=1/random-partitioner gate lane (one row per
    SCENARIOS entry); the fleet runner passes its extra matrix points
    via `fleet`, which lands verbatim under the "fleet" key."""
    wins = [r["scenario"] for r in rows
            if r["scenario"] in NEW_SCENARIOS + WORKLOAD_SCENARIOS
            and r["tec"][GATE_ENV]["gain"]["mean"] > 0]
    result = {
        "experiment": "exp6_scenarios",
        "config": dict(SCALES[scale], n_lp=N_LP, scale=scale,
                       replicas=n_rep,
                       interaction_bytes=INTERACTION_BYTES,
                       migration_bytes=MIGRATION_BYTES,
                       gate_env=GATE_ENV, epidemic=EPI),
        "results": rows,
        "gate": {
            "gaia_wins_on": wins,
            "n_new_scenarios_gaia_wins": len(
                [w for w in wins if w in NEW_SCENARIOS]),
            # machine-independent paired gains (mean/std/ci95/n stats
            # dicts) tracked by benchmarks/compare.py, which fails only
            # when the baseline and candidate intervals separate
            "tec_gain_by_scenario": {
                r["scenario"]: r["tec"][GATE_ENV]["gain"] for r in rows},
        },
    }
    if fleet is not None:
        result["fleet"] = fleet
    return result


def write_and_gate(result: dict) -> dict:
    """Persist the document, then enforce the acceptance gate (after
    writing, so a gate failure still leaves the evidence on disk)."""
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    for r in result["results"]:
        assert r["grid_overflow_on"] == 0.0 and r["grid_overflow_off"] == 0.0, \
            f"grid overflow on {r['scenario']}: clustered capacity too tight"
    mob_wins = [w for w in result["gate"]["gaia_wins_on"]
                if w in NEW_SCENARIOS]
    assert len(mob_wins) >= 2, \
        f"GAIA won TEC({GATE_ENV}) only on {mob_wins}; need >= 2 of " \
        f"{NEW_SCENARIOS}"
    print(f"[exp6] OK (GAIA wins on {result['gate']['gaia_wins_on']}, "
          f"n={result['config']['replicas']}) -> {OUT}")
    return result


def main(scale: str = "quick", replicas=None):
    n_rep = default_replicas(scale, replicas)
    seeds = list(range(n_rep))
    rows = []
    for scen in SCENARIOS:
        row = run_cell(scale, scen, seeds)
        rows.append(row)
        print_row(row)
    return write_and_gate(assemble(rows, scale, n_rep))


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[exp6] compile cache: {use_compile_cache()}")
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scale", nargs="?", default="quick",
                    choices=["quick", "full"])
    ap.add_argument("--replicas", type=int, default=None)
    a = ap.parse_args()
    main(a.scale, a.replicas)
