"""The window's counter read: one transfer of a summary the window
program computes itself.

`Engine.step` reads its counters from the `engine.window_summary` its
window program returns (one flat buffer, one device-to-host transfer).
Contracts enforced here, for each runner and config:

* the counters equal `engine.series_counters` applied eagerly to the
  same window's series, key for key and bit for bit;
* they equal a plain numpy read of that series: every whole-number
  counter its float64 sum exactly, the flow matrices their int64 sums
  as lists of ints, the means within f32 rounding;
* a second window of the same length compiles nothing.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core.abm import ABMConfig
from repro.core.engine import EngineConfig
from repro.core.heuristics import HeuristicConfig
from repro.core.service import Engine
from repro.obs import runtime as obs_runtime
from repro.obs.config import ObsConfig

ABM = ABMConfig(n_se=96, n_lp=4, area=1000.0, speed=5.0,
                interaction_range=80.0, p_interact=0.3)
BASE = EngineConfig(abm=ABM, heuristic=HeuristicConfig(mf=1.2, mt=5))
STEPS = 5

#: case -> (config, replica seeds or None for one resident world)
CASES = {
    "oracle": (BASE, None),
    "open_world": (dataclasses.replace(BASE, open_world=True, n_active=80),
                   None),
    "epidemic": (dataclasses.replace(BASE, abm=dataclasses.replace(
        ABM, workload="epidemic", epi_beta=0.4, epi_boost=4.0,
        epi_seed_frac=0.05)), None),
    # drain_every beyond both windows: the ring never wraps, so the
    # replay below files nothing into any telemetry session
    "telemetry": (dataclasses.replace(
        BASE, obs=ObsConfig(enabled=True, drain_every=64)), None),
    "batched": (BASE, [3, 4, 5]),
    "lp_device": (dataclasses.replace(BASE, sharding="lp_device",
                                      n_devices=2), None),
}

#: a plain numpy read of the series, the reference: counter -> series key
SUMS = {k: k for k in ("local_msgs", "remote_msgs", "migrations",
                       "heu_evals", "grid_overflow", "repartitions",
                       "shard_overflow")}
MEANS = {"mean_lcr": "lcr", "mean_pop": "pop", "mean_infected": "infected",
         "mean_halo_frac": "halo_frac"}
FLOWS = ("lp_flows", "mig_flows", "wire_flows")


def numpy_counters(series) -> dict:
    """The window's counters by numpy: float64 sums of the whole-number
    series (exact), float64 means, int64 flow sums."""
    s = {k: np.asarray(v) for k, v in series.items()}
    ref = {c: float(s[k].astype(np.float64).sum())
           for c, k in SUMS.items() if k in s}
    ref.update({c: float(s[k].astype(np.float64).mean())
                for c, k in MEANS.items() if k in s})
    if "infected" in s:
        ref["final_infected"] = float(s["infected"][-1])
    for k in FLOWS:
        if k in s:
            ref[k] = s[k].astype(np.int64).sum(axis=0).tolist()
    if "wire_flows" in s:
        ref["bytes_on_wire"] = float(s["wire_flows"].astype(np.int64).sum())
    return ref


def _engine(case) -> Engine:
    """An engine a window in, every program of `step(STEPS)` compiled."""
    cfg, seeds = CASES[case]
    e = Engine(cfg)
    if seeds:
        e.init(seeds=seeds)
    else:
        e.init(seed=5)
    e.step(STEPS)
    return e


@pytest.mark.parametrize("case", list(CASES))
def test_step_counters_equal_eager_read(case):
    cfg, seeds = CASES[case]
    e = _engine(case)
    before = e.state
    got = e.step(STEPS)
    # the same window again, from the same state, for its series
    with obs_runtime.use(None):
        if seeds:
            _, series, _ = eng._dispatch_window_batch(before, cfg, STEPS)
            reps = [eng.replica_series(series, r) for r in range(len(seeds))]
        else:
            _, series, _ = eng._dispatch_window(before, cfg, STEPS)
            reps, got = [series], [got]
    assert len(got) == len(reps)
    for counters, ser in zip(got, reps):
        eager = eng.series_counters(ser)
        assert counters.keys() == eager.keys()
        for k, v in eager.items():
            assert counters[k] == v, k
        ref = numpy_counters(ser)
        assert counters.keys() == ref.keys()
        for k, v in ref.items():
            if k in MEANS:
                assert counters[k] == pytest.approx(v, rel=1e-6), k
            else:
                assert counters[k] == v, k
        for k in FLOWS:
            if k in counters:
                assert all(type(x) is int for row in counters[k]
                           for x in row), k
        assert counters["migrations"] > 0  # a non-trivial window


@pytest.mark.parametrize("case", ["oracle", "batched", "lp_device"])
def test_second_window_compiles_nothing(case):
    e = _engine(case)
    if case == "lp_device":
        # `init_sharded` leaves the state off the mesh and the first
        # window returns it on the mesh: two input shardings, so the
        # sharded window compiles twice before it is steady
        e.step(STEPS)
    compiled = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        e.step(STEPS)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiled == []
