#!/usr/bin/env python3
"""Smoke run of the GAIA engine on a TPU, through `repro.core.Engine`.

One chip (the default), five phases in one process:

  device    platform, device kind and count; anything but a TPU exits
            non-zero here, with no fallback to the CPU.
  anchor    the paper's evaluation model at its published size (10 000
            SEs, 4 LPs, 10 000^2 torus, range 250, pi 0.2, speed 11,
            heuristic #1 with MF 1.2 / MT 10, `grid` backend, 3 600
            steps), GAIA off and on. GAIA on must raise the mean LCR,
            migrate, and never overflow the grid (the paper's Fig. 5).
  backends  the anchor's state for a short horizon on `dense`, `grid`,
            `pallas` and `pallas_grid`: per-step series and final state
            bit-identical, and the Pallas steps' compiled HLO must hold
            the Mosaic kernel (`tpu_custom_call`), not the interpreter.
  service   an open-world engine (10 000 slots, 8 000 live): windows of
            steps interleaved with arrive/depart batches and queries; the
            neighbour and region answers are checked against a plain
            host-side brute force over the device state.
  scale     the benchmarks/scale_smoke.py configuration at 10^6 SEs
            (hotspot mobility, 512 MB budget) for a few steps: no grid
            overflow, peak device memory printed.

`--chips 4` runs only the distributed-simulation layer: the anchor at
D = 4 under `sharding="lp_device"` against the single-device oracle on
the same seed, final state and every per-step series compared bit for
bit, over a horizon cut to SHARDED_STEPS.

Every line before the last names what it reports. Wall times are smoke
timings of a single run, not benchmark metrics: `compile_s` sums JAX's
own lowering and XLA compile events (a persistent-cache hit counts as
its read time), `run_s` is the rest of the call's wall, tracing
included. The last
line is one JSON object: {"ok": true, "device": {"platform", "kind",
"count"}}. Any failed check exits non-zero.

    python chip_smoke.py [--chips 1|4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
BACKENDS = ("dense", "grid", "pallas", "pallas_grid")
PARITY_STEPS = 50
SERVICE_LIVE = 8_000  # of the anchor's 10 000 slots
SERVICE_WINDOWS, SERVICE_WINDOW_STEPS, CHURN_BATCH = 3, 20, 256
SCALE_N = 1_000_000
SCALE_STEPS = 3
# the --chips 4 horizon, cut from the anchor's 3 600 steps: GAIA's
# migration burst and the halo traffic both start in the first steps,
# and a bit-level divergence shows as soon as it happens
SHARDED_STEPS = 100


class SmokeFailure(RuntimeError):
    pass


_compile_s = [0.0]
# lowering to HLO and the XLA compile (or its persistent-cache read);
# tracing is left out, since nested jits report nested trace events
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


class Timer:
    """Wall seconds of a block, split into JAX compile time and the rest."""

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = _compile_s[0] - self.c0
        self.run = self.wall - self.compile

    def fields(self, prefix: str = "") -> dict:
        return {f"{prefix}compile_s": round(self.compile, 3),
                f"{prefix}run_s": round(self.run, 3),
                "timing": "smoke-not-benchmark"}


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def device_check(chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", platform=dev["platform"], kind=repr(dev["kind"]),
        count=dev["count"])
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev['platform']!r}); this script runs on a TPU only",
              file=sys.stderr)
        sys.exit(2)
    if dev["count"] < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
              f"JAX sees {dev['count']}", file=sys.stderr)
        sys.exit(2)
    return dev


def peak_mem_mb():
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else round(peak / 2**20, 1)


def host(x):
    if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
        x = jax.random.key_data(x)
    return np.asarray(x)


def mismatches(a: dict, b: dict, keys) -> dict:
    """{key: largest absolute difference} over the keys whose arrays
    are not equal (shape mismatches report inf)."""
    bad = {}
    for k in keys:
        x, y = host(a[k]), host(b[k])
        if x.shape != y.shape:
            bad[k] = float("inf")
        elif not np.array_equal(x, y):
            bad[k] = float(np.max(np.abs(x.astype(np.float64)
                                         - y.astype(np.float64))))
    return bad


def timed_run(cfg, seed: int = 0):
    """Engine(cfg).run on one seed, blocked until the device is done."""
    from repro.core.service import Engine
    with Timer() as t:
        st, series, counters = Engine(cfg).run(seed=seed)
        jax.block_until_ready((st, series))
    return st, series, counters, t


def anchor_phase(cfg) -> None:
    res = {}
    for gaia in (False, True):
        _, _, c, t = timed_run(dataclasses.replace(cfg, gaia_on=gaia))
        res[gaia] = c
        say("anchor", gaia="on" if gaia else "off", n_se=cfg.abm.n_se,
            steps=cfg.timesteps, mean_lcr=c["mean_lcr"],
            migrations=c["migrations"], grid_overflow=c["grid_overflow"],
            run_ms_per_step=round(1e3 * t.run / cfg.timesteps, 4),
            **t.fields())
    off, on = res[False], res[True]
    check(on["mean_lcr"] > off["mean_lcr"],
          f"GAIA on LCR {on['mean_lcr']} <= off {off['mean_lcr']}")
    check(on["migrations"] > 0, "GAIA on made no migrations")
    check(off["grid_overflow"] == 0 and on["grid_overflow"] == 0,
          "grid overflow in the anchor run")
    say("anchor", ok=True, lcr_gain=on["mean_lcr"] - off["mean_lcr"],
        peak_mem_mb=peak_mem_mb())


def backends_phase(cfg, steps: int) -> None:
    import jax.numpy as jnp

    from repro.core import engine as eng
    from repro.core.service import Engine
    base = dataclasses.replace(cfg, timesteps=steps)
    runs = {}
    for b in BACKENDS:
        b_cfg = dataclasses.replace(
            base, abm=dataclasses.replace(base.abm, proximity_backend=b))
        st, series, c, t = timed_run(b_cfg)
        runs[b] = (st, series)
        mosaic = "n/a"
        if b.startswith("pallas"):
            st0 = Engine(b_cfg).init(seed=0).state
            hlo = eng._compiled_window(b_cfg, steps).lower(
                st0, jnp.float32(b_cfg.heuristic.mf)).compile().as_text()
            mosaic = "tpu_custom_call" in hlo
            # the CPU lowering is the interpreter by design (CPU rehearsal)
            check(mosaic or jax.default_backend() == "cpu",
                  f"{b}: compiled step has no tpu_custom_call (the Pallas "
                  "interpreter ran, not Mosaic)")
        say("backends", backend=b, steps=steps, mean_lcr=c["mean_lcr"],
            migrations=c["migrations"], grid_overflow=c["grid_overflow"],
            mosaic_kernel=mosaic, **t.fields())
    ref_st, ref_series = runs["dense"]
    for b in BACKENDS[1:]:
        st, series = runs[b]
        bad = mismatches(ref_series, series, ref_series)
        bad |= mismatches(ref_st, st, ref_st)
        check(not bad, f"{b} differs from dense: {bad}")
    say("backends", ok=True, bit_identical=True, peak_mem_mb=peak_mem_mb())


def brute_neighbors(pos, live, i: int, area: float, rng: float) -> list:
    """Plain host reference: live SEs within `rng` of SE i on the torus
    (float32, the engine's per-pair expression)."""
    d = np.abs(pos - pos[i])
    d = np.minimum(d, np.float32(area) - d)
    d2 = d[:, 0] ** 2 + d[:, 1] ** 2
    hit = live & (d2 <= np.float32(rng * rng))
    hit[i] = False
    return sorted(int(j) for j in np.flatnonzero(hit))


def service_phase(cfg, n_live: int, windows: int, window_steps: int,
                  batch: int) -> None:
    from repro.core.service import Engine
    cfg = dataclasses.replace(cfg, open_world=True, n_active=n_live)
    rs = np.random.default_rng(0)
    checked = 0
    with Timer() as t:
        e = Engine(cfg).init(seed=0)
        for w in range(windows):
            checked += service_window(e, rs, w, window_steps, batch, n_live)
        check(e.metrics()["grid_overflow"] == 0,
              "grid overflow in the service run")
    say("service", ok=True, neighbor_answers_checked=checked,
        peak_mem_mb=peak_mem_mb(), **t.fields())


def service_window(e, rs, w: int, steps: int, batch: int,
                   n_live: int) -> int:
    """One window of steps, a depart and an arrive batch, then queries
    checked against a host brute force; returns the neighbour answers
    checked."""
    area, rng_ = e.cfg.abm.area, e.cfg.abm.interaction_range
    c = e.step(steps)
    e.depart(rs.choice(e.live_ids(), batch, replace=False))
    new = e.arrive({"pos": rs.uniform(0, area, (batch, 2))})
    check(e.population() == n_live, "population drifted under churn")
    probe = [int(i) for i in rs.choice(e.live_ids(), 6, replace=False)]
    probe += new[:2]
    answer = e.query_neighbors(probe)
    pos = np.asarray(e.state["pos"])
    live = np.asarray(e.state["lp"]) >= 0
    check(sorted(np.flatnonzero(live).tolist()) == e.live_ids(),
          "device live mask and host live set disagree")
    for i in probe:
        want = brute_neighbors(pos, live, i, area, rng_)
        check(answer[i] == want,
              f"query_neighbors({i}) = {answer[i]} != brute force {want}")
    x0, y0 = rs.uniform(0, area, 2)
    bbox = (x0, y0, (x0 + area / 5) % area, (y0 + area / 5) % area)
    ids = e.query_region(bbox)

    def inside(v, lo, hi):
        return ((v >= lo) & (v <= hi)) if lo <= hi else \
            ((v >= lo) | (v <= hi))
    want = np.flatnonzero(live & inside(pos[:, 0], bbox[0], bbox[2])
                          & inside(pos[:, 1], bbox[1], bbox[3]))
    check(ids == want.tolist(), "query_region differs from brute force")
    lcr = e.query_lcr()
    check(np.isfinite(lcr) and 0.0 <= lcr <= 1.0, f"query_lcr {lcr}")
    say("service", window=w, steps=steps, mean_lcr=c["mean_lcr"],
        migrations=c["migrations"], grid_overflow=c["grid_overflow"],
        departed=batch, arrived=batch, population=e.population(),
        query_lcr=round(lcr, 4), region_hits=len(ids))
    return len(probe)


def scale_phase(cfg, steps: int) -> None:
    from repro.core.service import Engine
    spec = cfg.abm.grid_spec()
    with Timer() as t_init:
        e = Engine(cfg).init(seed=0)
        jax.block_until_ready(e.state)
    with Timer() as t:
        c = e.step(steps)
        jax.block_until_ready(e.state)
    check(c["grid_overflow"] == 0,
          f"grid overflow at N={cfg.abm.n_se}: {c['grid_overflow']}")
    say("scale", n_se=cfg.abm.n_se, grid=f"{spec.ncell}x{spec.ncell}",
        capacity=spec.capacity, mem_budget_mb=cfg.mem_budget_mb,
        steps=steps, mean_lcr=c["mean_lcr"], grid_overflow=0,
        peak_mem_mb=peak_mem_mb(), **(t_init.fields("init_") | t.fields()))
    say("scale", ok=True)


def sharded_phase(cfg, n_dev: int) -> None:
    from repro.parallel import lp_shard
    s_cfg = dataclasses.replace(cfg, sharding="lp_device", n_devices=n_dev)
    spec = lp_shard.make_shard_spec(s_cfg)
    mesh_devs = {d.id for d in lp_shard.make_mesh(spec).devices.flat}
    say("sharded", n_devices=spec.n_dev, mesh_device_ids=sorted(mesh_devs),
        slots_per_device=spec.cap, halo_capacity=spec.halo_cap)
    check(spec.n_dev == n_dev and len(mesh_devs) == n_dev,
          f"mesh spans {len(mesh_devs)} devices, not {n_dev}")

    ost, oseries, oc, t_o = timed_run(cfg)
    sst, sseries, sc, t_s = timed_run(s_cfg)
    ran_on = len(sst["pos"].sharding.device_set)
    say("sharded", steps=cfg.timesteps, state_device_count=ran_on,
        mean_lcr_oracle=oc["mean_lcr"], mean_lcr_sharded=sc["mean_lcr"],
        migrations_oracle=oc["migrations"], migrations=sc["migrations"],
        halo_frac=sc["mean_halo_frac"], bytes_on_wire=sc["bytes_on_wire"],
        shard_overflow=sc["shard_overflow"], peak_mem_mb=peak_mem_mb(),
        **(t_o.fields("oracle_") | t_s.fields("sharded_")))
    check(ran_on == n_dev, f"sharded state placed on {ran_on} devices")
    state_keys = sorted(set(ost) & set(sst))
    series_keys = sorted(set(oseries) & set(sseries))
    bad = mismatches(oseries, sseries, series_keys)
    bad |= {f"state.{k}": v
            for k, v in mismatches(ost, sst, state_keys).items()}
    for k, v in sorted(bad.items()):
        say("sharded", mismatch=k, max_abs_diff=v)
    say("sharded", compared_state_keys=",".join(state_keys),
        compared_series_keys=",".join(series_keys),
        bit_identical=not bad)
    check(sc["shard_overflow"] == 0, "shard overflow in the sharded run")
    check(not bad, f"lp_device at D={n_dev} differs from the oracle on "
                   f"{sorted(bad)}")
    say("sharded", ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases (default); 4: only the "
                         "lp_device path across four chips")
    args = ap.parse_args(argv)

    dev = device_check(args.chips)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.common import engine_cfg
    from benchmarks.scale_smoke import scale_cfg
    from repro.compile_cache import use_compile_cache
    say("compile_cache", dir=use_compile_cache())

    paper = engine_cfg("paper")
    with Timer() as t:
        if args.chips == 4:
            sharded_phase(dataclasses.replace(paper, timesteps=SHARDED_STEPS),
                          n_dev=4)
        else:
            anchor_phase(paper)
            backends_phase(paper, PARITY_STEPS)
            service_phase(paper, SERVICE_LIVE, SERVICE_WINDOWS,
                          SERVICE_WINDOW_STEPS, CHURN_BATCH)
            scale_phase(scale_cfg(SCALE_N), SCALE_STEPS)
    say("total", wall_s=round(t.wall, 3), peak_mem_mb=peak_mem_mb(),
        **t.fields())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
