"""Runtime telemetry subsystem (repro.obs) contract tests.

Two hard invariants from DESIGN.md §Observability:

* telemetry-off is a true zero-op — an obs-disabled config shares the
  memoized compiled executable with a config that never heard of
  telemetry (cache identity, not just equal results);
* telemetry-on never perturbs the run — state and per-step series stay
  *bit-identical* with obs on vs off, on both execution layers (the
  ring rides the scan carry; the step math never reads it).

Plus the drain correctness surface: the async ring-drain ledger must
reproduce the per-step series exactly (every step filed once, correct
stamps) for any window/drain_every alignment, and events must carry
exact step stamps.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core.abm import ABMConfig
from repro.core.engine import (EngineConfig, _compiled_window, run,
                               run_window, window_key_cfg)
from repro.core.heuristics import HeuristicConfig
from repro.obs import (EVENT_KINDS, JsonlSink, MemorySink, ObsConfig,
                       Telemetry, ledger_keys, prometheus_text, runtime)
from repro.core.service import Engine

ABM = ABMConfig(n_se=96, n_lp=4, area=1000.0, speed=5.0,
                interaction_range=80.0, p_interact=0.3)
BASE = EngineConfig(abm=ABM, heuristic=HeuristicConfig(mf=1.2, mt=5),
                    gaia_on=True, timesteps=24)
OBS = ObsConfig(enabled=True, drain_every=5)

STATE_KEYS = ("pos", "waypoint", "lp", "ring", "ptr", "last_mig")
SERIES_KEYS = ("lcr", "local_msgs", "remote_msgs", "migrations",
               "heu_evals")


def _obs_run(cfg, seed=7):
    """run() with a telemetry session current; returns (result, tele)."""
    tele = Telemetry(cfg)
    with runtime.use(tele):
        out = run(jax.random.key(seed), cfg)
    return out, tele


# ---------------------------------------------------------------------------
# invariant 1: telemetry-on is invisible to the simulation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    BASE,
    dataclasses.replace(BASE, sharding="lp_device", n_devices=2),
    dataclasses.replace(BASE, sharding="lp_device", n_devices=4),
], ids=["oracle", "lp_device-2", "lp_device-4"])
def test_bit_identity_on_vs_off(cfg):
    st0, s0, c0 = run(jax.random.key(7), cfg)
    (st1, s1, c1), tele = _obs_run(
        dataclasses.replace(cfg, obs=OBS), seed=7)
    for k in STATE_KEYS:
        np.testing.assert_array_equal(np.asarray(st0[k]),
                                      np.asarray(st1[k]), err_msg=k)
    for k in SERIES_KEYS:
        np.testing.assert_array_equal(np.asarray(s0[k]),
                                      np.asarray(s1[k]), err_msg=k)
    assert c0["mean_lcr"] == c1["mean_lcr"]
    assert len(tele.ledger) == cfg.timesteps  # and it actually observed


# ---------------------------------------------------------------------------
# invariant 2: telemetry-off is a zero-op (compiled-cache identity)
# ---------------------------------------------------------------------------

def test_disabled_obs_shares_compiled_executable():
    """A config carrying a *disabled* ObsConfig with non-default knobs
    must hit the very same memoized executable as the pristine config:
    window_key_cfg normalizes disabled obs away, so telemetry-off is
    provably not "the same program with dead branches" but the
    identical compiled object."""
    pristine = window_key_cfg(BASE)
    tweaked = window_key_cfg(dataclasses.replace(
        BASE, obs=ObsConfig(enabled=False, drain_every=3, mig_burst=50)))
    assert tweaked == pristine
    assert _compiled_window(tweaked, 8) is _compiled_window(pristine, 8)


def test_enabled_obs_compiles_apart():
    on = window_key_cfg(dataclasses.replace(BASE, obs=OBS))
    assert on != window_key_cfg(BASE)


# ---------------------------------------------------------------------------
# ledger drain correctness
# ---------------------------------------------------------------------------

def test_ledger_reproduces_series():
    """Drained rows must equal the per-step series the scan returns
    anyway — same counters, exact step stamps, one row per step."""
    cfg = dataclasses.replace(BASE, obs=OBS)
    (_, series, _), tele = _obs_run(cfg)
    led = tele.ledger
    assert tuple(led.keys) == ledger_keys(cfg)
    np.testing.assert_array_equal(led.column("step"),
                                  np.arange(cfg.timesteps, dtype=float))
    for k in ("lcr", "local_msgs", "remote_msgs", "migrations",
              "heu_evals"):
        np.testing.assert_array_equal(led.column(k),
                                      np.asarray(series[k], np.float64),
                                      err_msg=k)
    # per-LP slot load: closed world, so loads partition the population
    loads = np.stack([led.column(f"lp_load_{i}")
                      for i in range(cfg.abm.n_lp)])
    np.testing.assert_array_equal(loads.sum(axis=0),
                                  np.full(cfg.timesteps, cfg.abm.n_se))
    st = led.summary()["lcr"]
    assert st["n"] == cfg.timesteps
    # streaming mean accumulates in f64 over f32 rows; the series mean
    # reduces in f32 — equal up to f32 rounding only
    assert abs(st["mean"] - float(np.mean(series["lcr"]))) < 1e-6


def test_drain_every_is_only_batching():
    """drain_every changes *when* rows reach the host, never *what*
    rows: ledgers at drain_every=1 and =10 must be identical."""
    rows = []
    for de in (1, 10):
        cfg = dataclasses.replace(
            BASE, obs=ObsConfig(enabled=True, drain_every=de))
        _, tele = _obs_run(cfg)
        rows.append(tele.ledger.rows())
    np.testing.assert_array_equal(rows[0], rows[1])


def test_misaligned_windows_drain_exactly_once():
    """Windows whose length is not a multiple of drain_every exercise
    the tail flush and the stamp filter: stale slots from the previous
    window must not re-file, and no step may be lost or duplicated."""
    cfg = dataclasses.replace(BASE, timesteps=0,
                              obs=ObsConfig(enabled=True, drain_every=5))
    from repro.core.engine import _init_engine
    state = _init_engine(jax.random.key(7), cfg)
    tele = Telemetry(cfg)
    with runtime.use(tele):
        for _ in range(3):
            state, _ = run_window(state, cfg, 7)  # 7 % 5 != 0
    np.testing.assert_array_equal(tele.ledger.column("step"),
                                  np.arange(21, dtype=float))


def test_no_session_drops_blocks_without_error():
    cfg = dataclasses.replace(BASE, timesteps=10, obs=OBS)
    before = runtime.dropped_blocks
    run(jax.random.key(3), cfg)  # no session current
    jax.effects_barrier()
    assert runtime.dropped_blocks > before
    runtime.emit_event("tuner_move", 0, mf=1.0)  # silently ignored


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def test_threshold_events_have_exact_stamps():
    cfg = dataclasses.replace(
        BASE, repartition_every=8,
        obs=ObsConfig(enabled=True, drain_every=5, mig_burst=1))
    (_, series, _), tele = _obs_run(cfg)
    migs = np.asarray(series["migrations"])
    burst_steps = [e.step for e in tele.events.records("migration_burst")]
    assert burst_steps == [t for t in range(cfg.timesteps) if migs[t] >= 1]
    repart_steps = {e.step for e in tele.events.records("repartition")}
    # repartitions fire on the configured cadence (steps t > 0 with
    # t % every == 0); every emitted stamp must sit on it
    assert repart_steps and all(t > 0 and t % 8 == 0 for t in repart_steps)


def test_unknown_event_kind_rejected():
    tele = Telemetry(dataclasses.replace(BASE, obs=OBS))
    with pytest.raises(ValueError):
        tele.emit("not_a_kind", 0)
    assert "migration_burst" in EVENT_KINDS


def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "events.jsonl"
    cfg = dataclasses.replace(BASE, timesteps=10,
                              obs=ObsConfig(enabled=True, drain_every=5,
                                            mig_burst=1))
    tele = Telemetry(cfg, sinks=[JsonlSink(str(path))])
    with runtime.use(tele):
        run(jax.random.key(7), cfg)
    tele.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == len(tele.events.records())
    assert all(ln["kind"] in EVENT_KINDS and isinstance(ln["step"], int)
               for ln in lines)


# ---------------------------------------------------------------------------
# service surface
# ---------------------------------------------------------------------------

def test_engine_service_telemetry_and_churn_events():
    cfg = dataclasses.replace(
        BASE, timesteps=0, open_world=True, n_active=80,
        obs=ObsConfig(enabled=True, drain_every=5))
    eng = Engine(cfg, obs_sinks=[MemorySink()]).init(seed=0)
    eng.step(7)
    ids = eng.arrive({"pos": np.full((4, 2), 100.0)})
    eng.step(3)
    eng.depart(ids[:2])
    eng.step(2)
    led = eng.ledger()
    assert len(led) == 12
    pop = led.column("pop")
    assert pop[6] == 80 and pop[7] == 84 and pop[-1] == 82
    arrivals = eng.events("arrive")
    departs = eng.events("depart")
    assert [e.step for e in arrivals] == [7] and arrivals[0].data["count"] == 4
    assert [e.step for e in departs] == [10] and departs[0].data["count"] == 2
    text = eng.prometheus()
    assert "# TYPE gaia_lcr gauge" in text
    assert 'gaia_lp_load{lp="0"}' in text
    assert "gaia_population" in text and "gaia_events_total" in text
    eng.close()
    assert runtime.get_current() is not eng.telemetry


def test_engine_without_obs_has_no_telemetry_surface():
    eng = Engine(dataclasses.replace(BASE, timesteps=0)).init(seed=0)
    assert eng.telemetry is None
    with pytest.raises(RuntimeError):
        eng.ledger()
    with pytest.raises(RuntimeError):
        eng.prometheus()


def test_prometheus_text_shape():
    cfg = dataclasses.replace(BASE, timesteps=10, obs=OBS)
    _, tele = _obs_run(cfg)
    text = prometheus_text(tele, extra={"steps_total": 10})
    assert text.endswith("\n")
    assert "gaia_steps_total 10" in text
    assert "gaia_lcr_mean" in text
    for line in text.splitlines():
        assert line.startswith("# TYPE") or " " in line
