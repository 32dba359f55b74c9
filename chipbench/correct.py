"""Whether a run was correct: the plain reference replays every operation
the client made, from the same seed, and the program's answers, window
counters and final state are compared with the reference's.

Compared numbers, each with its limit (all comparisons are exact, so
every limit is 0):

  state_rows      SE rows whose final state (position and waypoint bit
                  for bit, LP, pending migration, heuristic window, last
                  migration) differs from the reference's; every row
                  counts when the step clock or the PRNG key differs
  counter_diffs   window counters that differ: local and remote
                  deliveries, migrations, heuristic evaluations and the
                  per-LP-pair flow matrices exactly, the mean LCR beyond
                  1e-6 (a float32 mean of float32 ratios)
  answer_diffs    service answers that differ: an arrival given a slot
                  that was not free, a neighbour list, an LCR beyond 1e-6
  overflow_steps  steps in which the program raised its own exactness
                  alarm (a grid cell or a shard buffer over capacity)
"""
from __future__ import annotations

import numpy as np

LIMITS = {"state_rows": 0, "counter_diffs": 0, "answer_diffs": 0,
          "overflow_steps": 0}

#: float tolerance of the LCR values, which are float32 ratios
LCR_TOL = 1e-6

_EXACT_COUNTERS = ("local_msgs", "remote_msgs", "migrations", "heu_evals")


def _counter_diffs(prog: dict, ref: dict) -> int:
    bad = sum(int(float(prog[k]) != float(ref[k])) for k in _EXACT_COUNTERS)
    for k in ("lp_flows", "mig_flows"):
        bad += int(not np.array_equal(np.asarray(prog[k], np.int64),
                                      np.asarray(ref[k], np.int64)))
    ref_lcr = float(np.mean(np.asarray(ref["lcr"], np.float64)))
    bad += int(abs(float(prog["mean_lcr"]) - ref_lcr) > LCR_TOL)
    return bad


def _state_rows(prog: dict, ref: dict) -> int:
    n = ref["lp"].shape[0]
    if int(prog["t"]) != int(ref["t"]) or \
            not np.array_equal(prog["key"], ref["key"]):
        return n
    bad = np.zeros(n, bool)
    for k in ("pos", "waypoint"):
        a = np.ascontiguousarray(prog[k], np.float32).view(np.uint32)
        b = np.ascontiguousarray(ref[k], np.float32).view(np.uint32)
        bad |= (a != b).any(-1)
    for k in ("lp", "pending_dst", "pending_eta", "last_mig"):
        bad |= np.asarray(prog[k]) != np.asarray(ref[k])
    bad |= (np.asarray(prog["ring"]) != np.asarray(ref["ring"])).any(
        axis=(0, 2))
    return int(bad.sum())


def check(cfg: dict, world: dict, seed: int, log: list, final: dict,
          device=None) -> dict:
    """Replay `log` on the reference; return {name: value} for each
    compared number of LIMITS."""
    from chipbench.reference import World
    m = cfg["engine"]["abm"]
    n = int(m["n_se"])
    live_n = int(world.get("n_active", 0)) or n
    w = World(cfg["engine"], seed, live_n, device=device)
    live = set(range(live_n))
    out = dict.fromkeys(LIMITS, 0)
    for entry in log:
        kind = entry[0]
        if kind == "step":
            c = entry[2]
            out["counter_diffs"] += _counter_diffs(c, w.step(entry[1]))
            out["overflow_steps"] += int(float(c.get("grid_overflow", 0))
                                         + float(c.get("shard_overflow", 0)))
        elif kind == "depart":
            w.depart(entry[1])
            live.difference_update(entry[1])
        elif kind == "arrive":
            pos, ids = entry[1], entry[2]
            ok = [i for i in dict.fromkeys(ids) if 0 <= i < n
                  and i not in live]
            out["answer_diffs"] += len(ids) - len(ok)
            w.arrive(ok, pos[[ids.index(i) for i in ok]],
                     w.stripe_lp(pos)[[ids.index(i) for i in ok]])
            live.update(ok)
        elif kind == "query_neighbors":
            ref = w.neighbors(entry[1])
            ans = entry[2]
            out["answer_diffs"] += sum(
                int(sorted(ans.get(i, [None])) != ref[i]) for i in entry[1])
        else:
            out["answer_diffs"] += int(abs(float(entry[1]) - w.lcr_now())
                                       > LCR_TOL)
    out["state_rows"] = _state_rows(final, w.numpy_state())
    return out


def verdict(values: dict) -> bool:
    return all(values[k] <= lim for k, lim in LIMITS.items())


def report_lines(values: dict) -> list:
    return [f"check {k}: {values[k]} (limit {LIMITS[k]})" for k in LIMITS]
