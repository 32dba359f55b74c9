"""§5.5 self-tuning benchmark: does intra-run MF tuning recover the
offline-sweep optimum without the sweep?

Compares priced TEC of (a) the best fixed MF found by the Fig. 8-style
offline sweep, (b) the intra-run self-tuner started from a bad MF, and
(c) the bad fixed MF itself — on the same model/seed.
"""
from __future__ import annotations

import dataclasses

import jax

from benchmarks.common import engine_cfg, write_csv
from repro.core.costmodel import SETUPS, wct
from repro.core.engine import run
from repro.core.selftune import SelfTuneConfig, intra_run_tune


def main(scale: str = "quick"):
    cfg = engine_cfg(scale, speed=5.0, mf=0.0)  # mf set per variant
    ts = cfg.timesteps
    params = SETUPS["distributed"]
    def price(c):
        return wct(c, params, cfg.abm.n_lp, ts,
                   interaction_bytes=1024, migration_bytes=32)["TEC"]
    key = jax.random.key(0)

    # (a) offline sweep (the paper's method)
    sweep = {}
    for mf in (1.1, 1.5, 3.0, 8.0):
        c = dataclasses.replace(cfg, heuristic=dataclasses.replace(
            cfg.heuristic, mf=mf))
        _, _, counters = run(key, c)
        sweep[mf] = price(counters)
        print(f"[selftune] fixed MF={mf:<4}: TEC {sweep[mf]:8.2f}s")
    best_mf = min(sweep, key=sweep.get)

    # (b) intra-run tuner from a bad start
    tc = SelfTuneConfig(window=max(50, ts // 8), mf0=8.0,
                        setup="distributed", interaction_bytes=1024,
                        migration_bytes=32)
    _, hist = intra_run_tune(key, cfg, tc, total_steps=ts)
    tuned_tec = sum(h[3] for h in hist) * tc.window
    steady = sum(h[3] for h in hist[-3:]) / 3 * ts  # post-warm-up rate
    print(f"[selftune] tuned (from MF=8): total TEC {tuned_tec:8.2f}s, "
          f"steady-state rate {steady:8.2f}s/run-equiv "
          f"(MF trajectory {[round(h[1], 2) for h in hist]})")

    rows = [("fixed_" + str(mf), tec) for mf, tec in sweep.items()]
    rows.append(("self_tuned_from_8.0_total", tuned_tec))
    rows.append(("self_tuned_steady_state", steady))
    path = write_csv("selftune.csv", "variant,tec_s",
                     [(n, round(t, 3)) for n, t in rows])

    # the tuner must beat its bad start decisively, and its post-warm-up
    # steady state must approach the offline-sweep optimum
    assert tuned_tec < sweep[8.0] * 0.9, (tuned_tec, sweep)
    assert steady < sweep[best_mf] * 1.15, (steady, sweep)
    print(f"[selftune] OK -> {path} (sweep best MF={best_mf})")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[selftune] compile cache: {use_compile_cache()}")
    main()
