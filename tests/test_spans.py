"""Program spans (repro.obs.trace) of the resident engine's calls.

Each call of `core.service.Engine` writes `gaia.<call>` and its stages
into the JAX profiler's trace. Contracts enforced here, on both
execution layers:

* the span tree of each call: its stages in order, nested in the call's
  span, every span of the call under the one `call` id;
* the counters they carry equal the counts worked out here: the
  proximity sweep's candidate slots (`walk_slots`), the counter read's
  device-to-host transfers (`fetches`), the churn batch sizes;
* spans never touch the programs: results are bit-identical with the
  profiler on and off, the lowered window program is the same, and the
  compiled-window memo gains no entry.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions

from repro.core import engine as eng_mod
from repro.core.abm import ABMConfig
from repro.core.engine import EngineConfig
from repro.core.heuristics import HeuristicConfig
from repro.core.service import Engine
from repro.parallel import lp_shard

ABM = ABMConfig(n_se=160, n_lp=4, area=3162.0, speed=11.0,
                interaction_range=250.0, p_interact=0.2)
BASE = EngineConfig(abm=ABM, heuristic=HeuristicConfig(mf=1.2, mt=5),
                    open_world=True, n_active=120)
LAYERS = {"none": BASE,
          "lp_device": dataclasses.replace(BASE, sharding="lp_device",
                                           n_devices=2)}
STEPS = 3
ARRIVALS = np.array([[10.0, 20.0], [1500.0, 700.0], [3000.0, 3100.0]],
                    np.float32)

CALLS = {
    "step": lambda e: e.step(STEPS),
    "arrive": lambda e: e.arrive({"pos": ARRIVALS}),
    "depart": lambda e: e.depart(e.live_ids()[:3]),
    "query_neighbors": lambda e: e.query_neighbors(e.live_ids()[:2]),
    "query_lcr": lambda e: e.query_lcr(),
}

#: the stages of each call, in order, as paths below the call's span
STAGES = {
    "step": ["dispatch", "wait", "readback"],
    "arrive": ["prepare", "apply", "sync"],
    "depart": ["prepare", "apply", "sync"],
    "query_neighbors": ["issue", "issue.grid", "issue.walk", "wait",
                        "readback"],
    "query_lcr": ["issue", "issue.counts", "issue.flows", "wait",
                  "readback"],
}


def record(fn, tmp_path):
    """Run `fn` under the profiler; returns (its result, the `gaia.*`
    host spans as (start, end, name, args, depth) in start order)."""
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith("gaia.")),
                         key=lambda x: (x[0], -x[1]))
            open_ends = []
            for s, e, name, args in evs:
                while open_ends and open_ends[-1] <= s:
                    open_ends.pop()
                spans.append((s, e, name, args, len(open_ends)))
                open_ends.append(e)
    return out, sorted(spans, key=lambda x: (x[0], x[4]))


def engine(layer: str) -> Engine:
    """An engine a window in, every program of the calls compiled."""
    e = Engine(LAYERS[layer]).init(seed=3)
    for fn in CALLS.values():
        fn(e)
    return e


def walk_slots(cfg) -> int:
    """Candidate slots the proximity sweep tests per step (one chunk at
    this size): on one device the cell-slab sweep, cells (with each cell
    row's two wrapped halo cells) x 9 neighbour cells x capacity^2 slot
    pairs; on the sharded layer the row walk of every device's `cap`
    local slots, rows x 9 cells x capacity."""
    grid = cfg.abm.grid_spec()
    if cfg.sharding == "lp_device":
        spec = lp_shard.make_shard_spec(cfg)
        return spec.n_dev * spec.cap * 9 * grid.capacity
    return grid.ncell * (grid.ncell + 2) * 9 * grid.capacity ** 2


@pytest.mark.parametrize("call", list(CALLS))
@pytest.mark.parametrize("layer", list(LAYERS))
def test_call_span_tree(layer, call, tmp_path):
    cfg = LAYERS[layer]
    e = engine(layer)
    out, spans = record(lambda: CALLS[call](e), tmp_path)

    top = [sp for sp in spans if sp[4] == 0]
    assert [sp[2] for sp in top] == [f"gaia.{call}"]
    t0, t1, _, args, _ = top[0]
    cid = args["call"]
    stages = STAGES[call]
    if call in ("arrive", "depart") and layer == "none":
        stages = stages[:-1]  # the oracle reads nothing back
    kids = [sp for sp in spans if sp[4] > 0]
    assert [sp[2] for sp in kids] == [f"gaia.{call}.{s}" for s in stages]
    for s, end, name, a, depth in kids:
        assert a["call"] == cid
        assert t0 <= s <= end <= t1
        assert depth == name.count(".") - 1
    kid = {sp[2].split(".", 2)[2]: sp[3] for sp in kids}

    if call == "step":
        assert args["n"] == STEPS
        assert kid["dispatch"]["walk_slots"] == walk_slots(cfg)
        # the window program's counter summary: one transfer per window
        assert kid["readback"]["fetches"] == 1
    elif call in ("arrive", "depart"):
        assert args["batch"] == 3 and args["padded"] == 4
    elif call == "query_neighbors":
        assert args["ids"] == 2


@pytest.mark.parametrize("layer", list(LAYERS))
def test_spans_leave_results_and_programs_alone(layer, tmp_path):
    cfg = LAYERS[layer]

    def script():
        e = Engine(cfg).init(seed=11)
        counters = e.step(STEPS)
        ids = e.arrive({"pos": ARRIVALS})
        e.depart(ids[:1] + e.live_ids()[:2])
        nbrs = e.query_neighbors(e.live_ids()[:4])
        lcr = e.query_lcr()
        state = {k: np.asarray(jax.random.key_data(v) if k == "key"
                               else v) for k, v in e.state.items()}
        return counters, ids, nbrs, lcr, state

    def lowered():
        # a fresh jit of the window program, so that it is traced anew
        st = Engine(cfg).init(seed=11).state
        build = (lp_shard._compiled_window_sharded if layer == "lp_device"
                 else eng_mod._compiled_window_cached).__wrapped__
        fn = build(eng_mod.window_key_cfg(cfg), STEPS)
        return fn.lower(st, np.float32(cfg.heuristic.mf)).as_text()

    off = script()
    memo = (eng_mod._compiled_window_cached.cache_info().currsize,
            lp_shard._compiled_window_sharded.cache_info().currsize)
    text_off = lowered()
    on, _ = record(script, tmp_path / "run")
    text_on, spans = record(lowered, tmp_path / "lower")

    assert spans == []  # tracing the window program opens no span
    assert text_on == text_off
    assert (eng_mod._compiled_window_cached.cache_info().currsize,
            lp_shard._compiled_window_sharded.cache_info().currsize) == memo
    for a, b in zip(off[:4], on[:4]):
        assert a == b
    for k, v in off[4].items():
        assert v.tobytes() == on[4][k].tobytes(), k
