"""Shared helpers for the paper-experiment benchmarks.

The paper's full scale (#SE=10000, 3600 timesteps, O(N^2) proximity) is
sized for a 16-core Xeon; this container is one CPU core, so every
experiment has a `scale` knob: "quick" (CI-sized, minutes) and "paper"
(the published parameters). Trends — not absolute seconds — are the
reproduction target either way; see DESIGN.md §Deviations.

Replicas: every performance claim in the paper is a statement about the
*expected* behaviour of a stochastic simulation, so the statistical
experiments take a `replicas` count (CLI `--replicas`; default 5 in
quick mode, 10 at mid/paper scale). The R seeds run in ONE batched
device pass (`Engine.run(seeds=...)`, vmap over the seed axis — replica
r is
bit-identical to a sequential run on seed r), and every reported metric
carries mean/std/ci95/n (src/repro/core/stats.py).
"""
from __future__ import annotations

import contextlib
import copy
import functools
import io
import json
import os
import subprocess
import sys
import time

import jax

from repro.core.abm import ABMConfig
from repro.core.engine import EngineConfig
from repro.core.heuristics import HeuristicConfig
from repro.core.service import Engine
from repro.core.stats import replica_stats, summarize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "results", "paper")


def on_accelerator() -> bool:
    """True when this process's JAX runs on an accelerator. It then
    holds the chips: a child process that needs one fails or hangs."""
    return jax.default_backend() != "cpu"


def run_child(code: str, n_dev: int) -> dict:
    """Run a benchmark section on `n_dev` devices and return the dict
    its code prints on a `RESULT <json>` line.

    On the CPU the code runs in a child process with `n_dev` forced host
    devices (the count is fixed when jax first starts). On an
    accelerator this process holds the chips, so the code runs here, on
    the first `n_dev` real devices, or the call refuses."""
    if on_accelerator():
        if jax.device_count() < n_dev:
            raise RuntimeError(
                f"section needs {n_dev} devices, this host has "
                f"{jax.device_count()} {jax.default_backend()} devices")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            exec(compile(code, "<benchmark section>", "exec"),
                 {"__name__": "__section__"})
        out = buf.getvalue()
    else:
        env = dict(
            os.environ,
            PYTHONPATH=os.pathsep.join(
                [os.path.join(REPO, "src"), REPO,
                 os.environ.get("PYTHONPATH", "")]),
            XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
            XLA_PYTHON_CLIENT_PREALLOCATE="false",
        )
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=3600,
                           env=env)
        if r.returncode != 0:
            raise RuntimeError(r.stdout + r.stderr)
        out = r.stdout
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in: {out!r}")


SCALES = {
    # n_se, timesteps, area (density kept at the paper's 1e-4 SE/unit^2)
    "quick": dict(n_se=1000, timesteps=400, area=3162.0),
    "mid": dict(n_se=3000, timesteps=900, area=5477.0),
    "paper": dict(n_se=10_000, timesteps=3600, area=10_000.0),
}

#: default replica counts per scale (n >= 3 in CI, which passes
#: --replicas 3 explicitly to bound the nightly budget). "full" is
#: exp6/exp7/exp8's name for their paper-sized sweep.
DEFAULT_REPLICAS = {"quick": 5, "mid": 10, "paper": 10, "full": 10}


def default_replicas(scale: str, override=None) -> int:
    """CLI --replicas override, else the per-scale default."""
    return int(override) if override else DEFAULT_REPLICAS.get(scale, 5)


def engine_cfg(scale: str, *, n_lp=4, speed=11.0, rng=250.0, pi=0.2,
               mf=1.2, mt=10, gaia=True, kind=1, timesteps=None,
               backend="grid"):
    """`speed` is in PAPER units (10000-side torus) and is scaled by
    side/10000 so the scaled-down world preserves the paper's *relative*
    dynamics (an SE crosses the world in the same number of timesteps —
    this is what sets the migration rate). `rng` stays absolute: SE
    density matches the paper's 1e-4/unit^2, so an absolute range keeps
    the paper's expected neighbor count (~19.6 at rng=250)."""
    s = SCALES[scale]
    f = s["area"] / 10_000.0
    return EngineConfig(
        abm=ABMConfig(n_se=s["n_se"], n_lp=n_lp, area=s["area"],
                      speed=speed * f, interaction_range=rng,
                      p_interact=pi, proximity_backend=backend),
        heuristic=HeuristicConfig(kind=kind, mf=mf, mt=mt),
        gaia_on=gaia,
        timesteps=timesteps or s["timesteps"],
    )


@functools.lru_cache(maxsize=None)
def _batch_counters(cfg: EngineConfig, seeds: tuple):
    """Hoisted cross-benchmark run cache: one batched engine run per
    distinct (config, seed-vector) per process. exp1's speed x MF grid
    overlaps tables23's MF sweep, and tables23 re-prices the same run
    across 9 (interaction, migration)-size combinations — pricing is
    cost-model arithmetic and must never re-run the engine. run_cfg
    deep-copies on the way out, so callers can never corrupt the
    cached counters."""
    _, _, reps = Engine(cfg).run(seeds=seeds)
    return reps


def run_cfg(cfg: EngineConfig, seed=0, replicas=1):
    """Run `replicas` consecutive seeds (seed..seed+R-1) in one batched
    pass. Returns a counters dict carrying

      * the replica-*mean* at every scalar metric key (trend code keeps
        reading c["mean_lcr"] / c["migrations"]),
      * "stats": {metric: {mean, std, ci95, n}} (the BENCH schema),
      * "reps": the per-replica counter dicts (matrix flow counters
        included — price each replica, then aggregate the prices),
      * "wall_s": wall time of this call (0 on a cache hit).
    """
    t0 = time.time()
    # deep copy: the cache's dicts are shared across benchmarks, and a
    # caller annotating/rounding a counters dict in place must corrupt
    # its own copy, never a later cache hit
    reps = copy.deepcopy(
        _batch_counters(cfg, tuple(range(seed, seed + replicas))))
    stats = summarize(reps)
    out = {k: v["mean"] for k, v in stats.items()}
    out["stats"] = stats
    out["reps"] = reps
    out["wall_s"] = time.time() - t0
    return out


def paired_stats(a_reps, b_reps, fn):
    """Stats of a per-replica *paired* derived metric: fn(a_r, b_r) per
    seed (e.g. dLCR or TEC gain ON vs OFF on the same seed) — pairing
    removes the between-seed variance the unpaired difference would
    carry."""
    return replica_stats([fn(a, b) for a, b in zip(a_reps, b_reps)])


def fmt_stat(st: dict, nd: int = 3) -> str:
    """'mean±ci95 (n=N)' log formatting for a stats dict."""
    return f"{st['mean']:.{nd}f}±{st['ci95']:.{nd}f}(n={st['n']})"


def write_csv(name: str, header: str, rows):
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, name)
    with open(path, "w") as f:
        f.write(header + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return path
