"""The trace reduction, on synthetic events and on a small trace recorded
on a v5e (data/small.xplane.pb, written by record_trace.py)."""
import gzip
import json
import os

import pytest

from chipbench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_nesting_self_time_and_leaves():
    # a while op [0, 100) holding ops [10, 30) and [40, 90); the second
    # holds [50, 60)
    ops = tr._nest([(0, 100, "%while.1 = w"), (10, 30, "%a.1 = x"),
                    (40, 90, "%b.2 = y"), (50, 60, "%c.3 = z")])
    by = {o[3]: o for o in ops}
    assert by["%while.1 = w"][2] == 100 - 20 - 50
    assert by["%b.2 = y"][2] == 40
    assert [o[3] for o in ops if o[4]] == ["%a.1 = x", "%c.3 = z"]


def test_union_and_gaps():
    d = tr.Device()
    d.busy = tr._union([(0, 10), (5, 20), (30, 40)])
    assert d.busy == [(0, 20), (30, 40)]
    assert d.busy_ns(0, 50) == 30
    assert d.gaps(0, 50) == [(20, 30), (40, 50)]
    assert d.busy_ns(15, 35) == 10


def test_hlo_scope_parse():
    text = """HloModule jit_fn
%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %sine.0 = f32[4]{0} sine(%p), metadata={op_name="w/step.proximity/sin"}
}
ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  ROOT %cosine.2 = f32[4]{0} cosine(%fusion.7), metadata={op_name="step.heuristic/cos"}
}
"""
    s = tr.parse_hlo_scopes(text)
    assert tr.phase_of(s["fusion.7"]) == "proximity"
    assert tr.phase_of(s["cosine.2"]) == "heuristic"


def test_named_gaps_take_the_overlapping_span():
    d = tr.Device()
    d.busy = [(0, 10), (50, 60)]
    t = tr.Trace({0: d}, [(0, 12, "step"), (12, 45, "query_lcr"),
                          (45, 60, "step")], {})
    assert t.window() == (0, 60)
    assert t.named_gaps(0) == [("query_lcr", 40)]


@pytest.fixture(scope="module")
def small():
    with gzip.open(os.path.join(DATA, "small.xplane.pb.gz")) as f:
        data = f.read()
    with open(os.path.join(DATA, "small.scopes.json")) as f:
        scopes = json.load(f)
    return tr.Trace.from_bytes(data, scopes)


def test_recorded_trace_reduces(small):
    assert set(small.devices) == {0}
    assert [n for _, _, n in small.spans] == ["step", "step"]
    w0, w1 = small.window()
    dev = small.devices[0]
    busy = dev.busy_ns(w0, w1)
    assert 0 < busy <= w1 - w0
    # the leaves' union, worked out a second way: a sweep over endpoints
    ends = sorted([(s, 1) for s, e, sf, n, leaf in dev.ops if leaf] +
                  [(e, -1) for s, e, sf, n, leaf in dev.ops if leaf])
    depth, last, total = 0, None, 0.0
    for t, d in ends:
        if depth > 0:
            total += max(0.0, min(t, w1) - max(last, w0))
        depth += d
        last = t
    assert abs(total - busy) < 1e-6 * (w1 - w0)
    gaps = sum(g for _, g in small.named_gaps(0))
    assert abs(gaps + busy - (w1 - w0)) < 1e-6 * (w1 - w0)


def test_recorded_trace_has_the_step_phases(small):
    phases = small.self_by_phase(0)
    for p in ("migrate", "mobility", "proximity", "accounting",
              "heuristic"):
        assert phases.get(p, 0) > 0, phases
    w0, w1 = small.window()
    assert sum(phases.values()) <= (w1 - w0)


def test_per_layer_readers_on_the_recorded_trace(small):
    """Every device-trace reader of the batch cells, on the recorded
    trace of two 5-step windows of 400 SEs."""
    from chipbench import device
    from chipbench.harness import Run, _load_reader
    from chipbench.tests.tiny import ROOT, TINY_ABM
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gaia-paper-10k.json")) as f:
        cfg = json.load(f)
    cfg["engine"]["abm"].update(TINY_ABM)
    w0, w1 = small.window()
    dev = small.devices[0]
    run = Run(cfg=cfg, trace=small, steps=10, spans=[], counters=[],
              peaks=device.peaks("TPU v5 lite"),
              busy_s=dev.busy_ns(w0, w1) / 1e9, window_s=(w1 - w0) / 1e9)

    def read(name):
        return _load_reader(ROOT, "metrics", name)(run)

    prox = read("phase.proximity_ms_per_step")
    assert prox == small.self_by_phase(0)["proximity"] / 1e6 / 10
    assert 0 < read("phase.heuristic_ms_per_step") < prox
    assert 0 < read("proximity.roofline_pct") < 100
    idle = read("device.idle_pct")
    assert 0 < idle < 100
    gap = read("driver.host_gap_ms_per_window")
    assert 0 < gap < (w1 - w0) / 1e6
    assert read("exchange.collective_ms_per_step") is None
