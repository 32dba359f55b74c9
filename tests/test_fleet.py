"""Fleet runner: matrix construction, executor contract, result merge.

These tests cover the deterministic scaffolding — the declarative cell
matrix, the RESULT-line child protocol, the schema of the merged
BENCH document, and the stub executors' loud refusal — without paying
for engine subprocesses; the cells themselves re-use
exp6_scenarios.run_cell, whose physics is covered by
tests/test_workloads.py and the nightly fleet run.
"""
import json
import os

import pytest

from benchmarks import exp6_scenarios as exp6
from benchmarks import fleet


def test_matrix_covers_the_declared_axes():
    cells = fleet.build_matrix("quick", n_rep=4)
    assert cells == fleet.build_matrix("quick", n_rep=4)  # deterministic
    gate = [c for c in cells if c.gate]
    assert [c.scenario for c in gate] == list(exp6.SCENARIOS)
    assert all(c.kind == "tec" and c.n_devices == 1
               and c.partitioner == "random" and len(c.seeds) == 4
               for c in gate)
    part_axis = [c for c in cells if c.kind == "tec" and not c.gate]
    assert {c.scenario for c in part_axis} == set(exp6.WORKLOAD_SCENARIOS)
    assert all(c.partitioner == "voronoi" and c.repartition_every > 0
               for c in part_axis)
    ident = [c for c in cells if c.kind == "identity"]
    assert {(c.scenario, c.n_devices) for c in ident} == {
        (s, d) for s in exp6.WORKLOAD_SCENARIOS for d in (2, 4)}


def test_cell_payload_round_trips_through_json():
    cell = fleet.build_matrix("quick", 3)[0]
    payload = json.loads(json.dumps(cell.payload()))
    assert payload["scenario"] == cell.scenario
    assert payload["seeds"] == list(cell.seeds)
    assert payload["gate"] is True


def test_parse_result_protocol():
    out = "noise\nRESULT {\"x\": 1}\ntrailing\n"
    assert fleet.parse_result(out, "c") == {"x": 1}
    with pytest.raises(RuntimeError, match="no RESULT line"):
        fleet.parse_result("compile log only\n", "c")


def test_stub_executors_refuse_loudly():
    with pytest.raises(NotImplementedError, match="container executor"):
        fleet.ContainerExecutor().run([])
    with pytest.raises(NotImplementedError, match="k8s executor"):
        fleet.K8sExecutor().run([])
    with pytest.raises(NotImplementedError):
        fleet.Executor().run([])
    assert set(fleet.EXECUTORS) == {"local", "container", "k8s"}


def test_local_executor_refuses_where_this_process_holds_the_chips(
        monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "on_accelerator", lambda: True)
    with pytest.raises(RuntimeError, match="holds the accelerator"):
        fleet.LocalExecutor(workers=1).run(
            fleet.build_matrix("quick", 1)[:1])


def test_run_child_section_stays_in_process_on_an_accelerator(monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "on_accelerator", lambda: True)
    code = 'import json, os\nprint("RESULT " + json.dumps(os.getpid()))\n'
    assert common.run_child(code, 1) == os.getpid()
    with pytest.raises(RuntimeError, match="needs 64 devices"):
        common.run_child(code, 64)


def _fake_row(scenario, gain):
    stats = {"mean": gain, "std": 0.0, "ci95": 0.0, "n": 2}
    return {"scenario": scenario, "n": 2,
            "grid_overflow_on": 0.0, "grid_overflow_off": 0.0,
            "tec": {env: {"gain": dict(stats)} for env in exp6.ENVS}}


def _fake_fleet_results():
    cells = fleet.build_matrix("quick", 2)
    results = []
    for c in cells:
        if c.kind == "tec":
            results.append({"cell": c.name, "kind": "tec", "gate": c.gate,
                            "row": _fake_row(c.scenario, 0.1)})
        else:
            results.append({"cell": c.name, "kind": "identity",
                            "match": True, "mismatch": [],
                            "shard_overflow": 0.0, "mean_lcr": 0.9,
                            "migrations": 3.0, "timesteps": 60,
                            "wall_s": 1.0})
    return cells, results


def test_merge_keeps_exp6_schema_and_adds_fleet_block():
    cells, results = _fake_fleet_results()
    doc = fleet.merge(cells, results, "quick", 2)
    # the compare.py-tracked surface is intact
    assert doc["experiment"] == "exp6_scenarios"
    assert [r["scenario"] for r in doc["results"]] == list(exp6.SCENARIOS)
    gains = doc["gate"]["tec_gain_by_scenario"]
    assert set(gains) == set(exp6.SCENARIOS)
    assert all({"mean", "std", "ci95", "n"} <= set(g)
               for g in gains.values())
    # the fleet block carries every matrix point, rows stripped
    assert len(doc["fleet"]["cells"]) == len(cells)
    assert all("row" not in c for c in doc["fleet"]["cells"])
    assert len(doc["fleet"]["identity"]) == 4
    assert len(doc["fleet"]["extra_tec"]) == len(exp6.WORKLOAD_SCENARIOS)


def test_merge_asserts_identity_divergence():
    cells, results = _fake_fleet_results()
    for r in results:
        if r["kind"] == "identity":
            r["match"], r["mismatch"] = False, ["pos"]
            break
    with pytest.raises(AssertionError, match="diverged from oracle"):
        fleet.merge(cells, results, "quick", 2)


def test_run_cell_payload_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown cell kind"):
        fleet.run_cell_payload({"kind": "nope", "scale": "quick",
                                "scenario": "rwp", "seeds": [0],
                                "partitioner": "random",
                                "repartition_every": 0, "n_devices": 1})
