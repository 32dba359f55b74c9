"""Experiment 5 (beyond-paper): LP-per-device sharded engine scaling.

Measures per-step wall-clock of the GAIA engine under
`sharding="none"` (single-device oracle) vs `sharding="lp_device"`
(parallel/lp_shard.py) at 1/2/4/8 forced host-platform devices, plus
the halo-shrink trajectory that shows GAIA physically reducing
inter-shard communication. Results land in BENCH_sharded.json at the
repo root (uploaded as a CI artifact).

Each device count runs in a fresh subprocess: XLA pins the device count
at first init, so `XLA_FLAGS=--xla_force_host_platform_device_count=N`
must be set before jax imports.

Honest-measurement notes:
  * every "device" here is a thread on the same CPU. On a single-core
    host, D>1 rows measure *orchestration overhead* (shard_map,
    collectives, slot indirection), not parallel speedup, so the
    unconditional acceptance gate is overhead at D=1: the sharded
    engine must not be slower than the oracle on one device. When the
    host has >= 2 cores (os.cpu_count), a second gate requires
    sharded D=4 to beat the oracle outright.
  * bytes_on_wire is the sparse exchange's exact transport count (see
    lp_shard's wire-accounting rules); the halo-shrink child asserts it
    falls monotonically (within ci95) as GAIA clusters the hotspot
    scenario.
  * timing excludes compilation (one full warm-up scan first) and uses
    a jitted fixed-length scan, the same shape the engine runs under.

    PYTHONPATH=src python benchmarks/exp5_sharded.py [quick|full]

quick: N=10k (CI-sized). full: N=50k (the gate scale).
"""
from __future__ import annotations

import json
import os
import sys

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmarks.common import run_child  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_sharded.json")

SCALES = {"quick": 10_000, "full": 50_000}
DEVICE_COUNTS = (1, 2, 4, 8)
STEPS = 3  # timed steps per measurement (one warm-up scan first)

_TIMING_CODE = """
import json, time
import jax
import jax.numpy as jnp
from repro.core.abm import ABMConfig
from repro.core.engine import EngineConfig, init_engine, step
from repro.core.heuristics import HeuristicConfig

mode, n_dev, n_se, steps = {mode!r}, {n_dev}, {n_se}, {steps}
cfg = EngineConfig(
    abm=ABMConfig(n_se=n_se, n_lp=8, area=10_000.0, speed=11.0,
                  interaction_range=250.0, p_interact=0.2),
    heuristic=HeuristicConfig(mf=1.2, mt=10),
    gaia_on=True, timesteps=steps, sharding=mode, n_devices=n_dev,
    mig_capacity=max(512, n_se // 4))  # early burst: ~N/8 admissions/step
st = init_engine(jax.random.key(0), cfg)

if mode == "lp_device":
    from repro.parallel import lp_shard
    spec = lp_shard.make_shard_spec(cfg)
    mesh = lp_shard.make_mesh(spec)
    def body(s, _):
        return lp_shard.step_sharded(s, cfg, spec, mesh)
else:
    def body(s, _):
        return step(s, cfg)

scan = jax.jit(lambda s: jax.lax.scan(body, s, None, length=steps))
# two warm-ups: the first compiles; feeding its output back changes the
# input shardings (device-committed arrays) and compiles a second cache
# entry — the steady-state executable every later call reuses
st2, series = scan(st)
jax.block_until_ready(st2)
st2, series = scan(st2)
jax.block_until_ready(st2)
# min over repetitions: the container's CPU share swings ~2x with
# neighbor load, and min is the standard noise-robust estimator for the
# ratio gates; the full rep distribution is also reported as
# mean/std/ci95/n (the BENCH schema)
from repro.core.stats import replica_stats
times = []
for _ in range(3):
    t0 = time.time()
    st2, series = scan(st2)
    jax.block_until_ready(st2)
    times.append((time.time() - t0) / steps)
dt = min(times)
out = dict(mode=mode, n_dev=n_dev, n_se=n_se, per_step_s=round(dt, 4),
           per_step_stats={{k: round(v, 4)
                            for k, v in replica_stats(times).items()}},
           devices=len(jax.devices()))
if mode == "lp_device":
    out["slots_per_dev"] = spec.cap
    out["overflow"] = float(series["shard_overflow"].sum())
    out["halo_frac"] = round(float(series["halo_frac"].mean()), 4)
    # exact transport bytes for one steady-state scan (halo rows +
    # migration rows + heuristic gathers; see lp_shard's accounting)
    out["bytes_on_wire"] = float(series["bytes_on_wire"].sum())
print("RESULT " + json.dumps(out))
"""

_HALO_CODE = """
import json
import jax
from repro.core.abm import ABMConfig
from repro.core.engine import EngineConfig, run
from repro.core.heuristics import HeuristicConfig
import dataclasses, numpy as np

from repro.core.stats import replica_stats

cfg = EngineConfig(
    abm=ABMConfig(n_se={n_se}, n_lp=8, area=10_000.0, speed=11.0,
                  interaction_range=250.0, p_interact=0.2,
                  mobility="hotspot", n_groups=8, group_radius=900.0),
    heuristic=HeuristicConfig(mf=1.2, mt=10),
    gaia_on=True, timesteps=80, sharding="lp_device", n_devices=4,
    mig_capacity=512)

def window_stats(x, w=10):
    return [{{k: round(v, 4) for k, v in replica_stats(
        [float(u) for u in x[i:i + w]]).items()}}
            for i in range(0, len(x), w)]

rows = {{}}
for gaia in (True, False):
    _, series, c = run(jax.random.key(1),
                       dataclasses.replace(cfg, gaia_on=gaia))
    h = np.asarray(series["halo_frac"])
    b = np.asarray(series["bytes_on_wire"])
    rows["gaia_on" if gaia else "gaia_off"] = dict(
        halo_frac_first10=window_stats(h)[0],
        halo_frac_last10=window_stats(h)[-1],
        bytes_on_wire_first10=window_stats(b)[0],
        bytes_on_wire_last10=window_stats(b)[-1],
        bytes_on_wire_windows=window_stats(b),
        mean_lcr=round(c["mean_lcr"], 4),
        overflow=c["shard_overflow"])
print("RESULT " + json.dumps(rows))
"""


def main(scale: str = "full"):
    n_se = SCALES[scale]
    rows = []
    row = run_child(_TIMING_CODE.format(mode="none", n_dev=1, n_se=n_se,
                                        steps=STEPS), 1)
    print(f"[exp5] none      D=1 {row['per_step_s']:.3f}s/step")
    rows.append(row)
    for d in DEVICE_COUNTS:
        row = run_child(_TIMING_CODE.format(mode="lp_device", n_dev=d,
                                            n_se=n_se, steps=STEPS), d)
        print(f"[exp5] lp_device D={d} {row['per_step_s']:.3f}s/step "
              f"(halo_frac {row['halo_frac']}, overflow {row['overflow']})")
        assert row["overflow"] == 0.0, row
        rows.append(row)

    halo = run_child(_HALO_CODE.format(n_se=min(n_se, 10_000)), 4)
    g_on = halo["gaia_on"]
    print(f"[exp5] halo shrink (D=4 hotspot, GAIA on): "
          f"{g_on['halo_frac_first10']['mean']} -> "
          f"{g_on['halo_frac_last10']['mean']}; wire "
          f"{g_on['bytes_on_wire_first10']['mean']:.0f} -> "
          f"{g_on['bytes_on_wire_last10']['mean']:.0f} B/step")
    # the neighbor-only exchange's physical claim: as GAIA clusters the
    # hotspot scenario, the measured bytes fall monotonically (within
    # each window's ci95 — single-seed windows are noisy)
    bw = g_on["bytes_on_wire_windows"]
    for a, b in zip(bw, bw[1:]):
        assert b["mean"] <= a["mean"] + a["ci95"] + b["ci95"], (a, b)
    assert (bw[-1]["mean"] + bw[-1]["ci95"]
            < bw[0]["mean"] - bw[0]["ci95"]), (bw[0], bw[-1])

    base = rows[0]["per_step_s"]
    sharded1 = next(r for r in rows if r["mode"] == "lp_device"
                    and r["n_dev"] == 1)["per_step_s"]
    sharded4 = next(r for r in rows if r["mode"] == "lp_device"
                    and r["n_dev"] == 4)["per_step_s"]
    result = {
        "experiment": "exp5_sharded",
        "config": dict(n_se=n_se, n_lp=8, steps=STEPS, scale=scale,
                       cpu_count=os.cpu_count(),
                       note="host devices share the host CPU: D>1 rows "
                            "only measure speedup when cores >= devices"),
        "results": rows,
        "halo_shrink_d4": halo,
        "sharded_overhead_at_d1": round(sharded1 / base, 3),
        "speedup_at_d4": round(base / sharded4, 3),
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    # acceptance gate: sharded on one device is no slower than the oracle
    assert sharded1 <= base * 1.05, (sharded1, base)
    # on parallel hardware the sparse halo must turn devices into actual
    # speedup; on a single-core container D>1 only measures orchestration
    # overhead, so the gate is conditional on the host having cores
    if (os.cpu_count() or 1) >= 2:
        assert sharded4 < base, (sharded4, base)
        print(f"[exp5] D=4 speedup {result['speedup_at_d4']}x")
    print(f"[exp5] OK (D=1 overhead {result['sharded_overhead_at_d1']}x) "
          f"-> {OUT}")
    return result


if __name__ == "__main__":
    print(f"[exp5] compile cache: {use_compile_cache()}")
    main(sys.argv[1] if len(sys.argv) > 1 else "full")
