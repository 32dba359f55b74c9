"""Experiment 10 (beyond-paper): telemetry overhead.

Measures the ISSUE-9 observability acceptance bars:

1. **Overhead** — the per-step metrics ledger (ring buffer in the scan
   carry, one async `jax.debug.callback` per `drain_every=10` steps,
   host-side ingestion into the streaming ledger) must cost < 10% wall
   clock on the quick config: `obs.overhead_ratio = t_on / t_off`, min
   over reps on both sides (exp8/exp9 flake-avoidance protocol). The
   timed region includes the host callback work — that is the cost a
   resident deployment actually pays.
2. **Non-perturbation** — the obs-on run must be *bit-identical* to the
   obs-off run on the same seed (per-step series compared exactly), and
   the drained ledger must reproduce the series it mirrors. Asserted
   here so the nightly gate re-proves it at bench scale, not just at
   test scale (tests/test_obs.py). The events JSONL from the overhead
   run lands in results/exp10_events.jsonl (CI artifact).

Results land in BENCH_obs.json; `obs.overhead_ratio` is tracked by
benchmarks/compare.py against BENCH_baseline/ (a time/time ratio —
TIMING_TOL width, machine-independent shape).

    PYTHONPATH=src python benchmarks/exp10_obs.py [quick|full]
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import engine_cfg  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.service import Engine  # noqa: E402
from repro.obs import ObsConfig, Telemetry, runtime  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "BENCH_obs.json")
RESULTS_DIR = os.path.join(REPO, "results")
EVENTS_OUT = os.path.join(RESULTS_DIR, "exp10_events.jsonl")

OVERHEAD_BOUND = 1.10  # ISSUE-9 bar: < 10% wall overhead at drain_every=10
DRAIN_EVERY = 10
TIME_REPS = {"quick": 3, "full": 5}

SERIES_KEYS = ("lcr", "local_msgs", "remote_msgs", "migrations",
               "heu_evals")


def overhead_section(scale: str):
    """Same seed, same config, obs off vs on: wall ratio + bit-identity
    + ledger-vs-series cross-check."""
    reps = TIME_REPS[scale]
    cfg_off = engine_cfg("quick")
    cfg_on = dataclasses.replace(
        cfg_off, obs=ObsConfig(enabled=True, drain_every=DRAIN_EVERY))

    # warm both compiled scans (they compile apart: the on-path carries
    # the ring; the off-path is the historical program)
    Engine(cfg_off).run(seed=0)
    Engine(cfg_on).run(seed=0)
    runtime.set_current(None)

    t_off, series_off = [], None
    for _ in range(reps):
        t0 = time.time()
        _, series_off, _ = Engine(cfg_off).run(seed=0)
        jax.block_until_ready(series_off)
        t_off.append(time.time() - t0)

    t_on, tele, series_on = [], None, None
    for _ in range(reps):
        eng = Engine(cfg_on)
        tele = eng.telemetry
        t0 = time.time()
        _, series_on, _ = eng.run(seed=0)
        jax.block_until_ready(series_on)
        jax.effects_barrier()  # count the in-flight drains too
        t_on.append(time.time() - t0)
        runtime.set_current(None)

    for k in SERIES_KEYS:  # bit-identity: telemetry never perturbs
        np.testing.assert_array_equal(
            np.asarray(series_off[k]), np.asarray(series_on[k]),
            err_msg=f"obs-on diverged from obs-off on {k}")
    # drain completeness: one ledger row per step, counters exact
    assert len(tele.ledger) == cfg_on.timesteps, \
        f"ledger {len(tele.ledger)} rows != {cfg_on.timesteps} steps"
    np.testing.assert_array_equal(
        tele.ledger.column("migrations"),
        np.asarray(series_on["migrations"], np.float64))

    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(EVENTS_OUT, "w", encoding="utf-8") as fh:
        for ev in tele.events.records():
            fh.write(json.dumps(ev.as_dict()) + "\n")

    ratio = min(t_on) / min(t_off)
    print(f"[exp10] overhead: off {min(t_off):.2f}s on {min(t_on):.2f}s "
          f"-> {ratio:.3f}x (bound < {OVERHEAD_BOUND}), "
          f"{len(tele.ledger)} ledger rows, "
          f"{len(tele.events.records())} events -> {EVENTS_OUT}")
    return {
        "drain_every": DRAIN_EVERY,
        "timesteps": cfg_on.timesteps,
        "t_off_s": [round(t, 3) for t in t_off],
        "t_on_s": [round(t, 3) for t in t_on],
        "overhead_ratio": round(ratio, 4),
        "overhead_bound": OVERHEAD_BOUND,
        "ledger_rows": len(tele.ledger),
        "events": len(tele.events.records()),
        "bit_identical": True,  # the asserts above would have raised
    }


def main(scale: str = "quick"):
    overhead = overhead_section(scale)

    result = {
        "experiment": "exp10_obs",
        "config": dict(scale=scale, backend=jax.default_backend(),
                       n_se=engine_cfg("quick").abm.n_se,
                       drain_every=DRAIN_EVERY),
        "obs": overhead,
        "gate": {
            "overhead_ratio": {"value": overhead["overhead_ratio"],
                               "bound": OVERHEAD_BOUND, "dir": "lower"},
        },
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)

    assert overhead["overhead_ratio"] < OVERHEAD_BOUND, \
        (f"telemetry overhead {overhead['overhead_ratio']:.3f}x "
         f"exceeds the {OVERHEAD_BOUND}x bar")
    print(f"[exp10] OK -> {OUT}")
    return result


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scale", nargs="?", default="quick",
                    choices=["quick", "full"])
    a = ap.parse_args()
    print(f"[exp10] compile cache: {use_compile_cache()}")
    main(a.scale)
