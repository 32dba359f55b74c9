"""Scenario fleet runner: the whole benchmark matrix as ONE invocation.

ReFrame-style split between *what* runs and *where* it runs: a fleet is
a list of declarative `FleetCell`s — matrix points over
{scenario x partitioner x device-count}, each priced on every
ExecutionEnvironment preset inside the cell — and an `Executor` decides
how a cell becomes a process. The local executor forks one subprocess
per cell (its own XLA runtime, its own forced host-device count for the
D axis) with bounded parallelism and collects each child's
``RESULT <json>`` line, the same protocol exp5 uses for its device
sweeps. Container/Kubernetes executors are declared behind the same
interface and raise NotImplementedError until a scheduler exists to
back them — the fleet definition will not change when they do.

Cells come in two kinds:

  * ``tec``  — the paired GAIA on/off TEC cell (exp6_scenarios.run_cell)
               for one scenario at one partitioner setting; the
               D=1/random-partitioner lane of these rows IS exp6's
               output and feeds the acceptance gate.
  * ``identity`` — oracle vs lp_device byte-equality for one scenario
               at one device count: the sharded-transparency invariant
               (tests/test_workloads.py proves it at unit scale; these
               cells re-prove it at benchmark scale on every nightly).

The merged document keeps exp6's BENCH_scenarios.json schema exactly
(results rows + gate.tec_gain_by_scenario, so benchmarks/compare.py and
the committed baselines keep working) and adds a ``fleet`` block with
every matrix point. This is the single nightly invocation: running
``fleet.py quick`` replaces the ad-hoc per-benchmark exp6 step.

    PYTHONPATH=src python benchmarks/fleet.py [quick|full]
        [--replicas R] [--workers W] [--executor local]
    # child mode (spawned by LocalExecutor, one per cell):
    PYTHONPATH=src python benchmarks/fleet.py --cell '<json>'
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

if __package__ in (None, ""):  # script invocation: python benchmarks/...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

#: state keys compared by identity cells (tests/test_sharding.py's
#: equivalence list plus the epidemic flag that reshards with the row)
IDENTITY_STATE_KEYS = ("pos", "waypoint", "mob", "mob_g", "lp", "ring",
                       "ptr", "since_eval", "last_mig", "epi")
IDENTITY_SERIES_KEYS = ("local_msgs", "remote_msgs", "migrations", "lcr")
IDENTITY_TIMESTEPS = {"quick": 60, "full": 120}
CHILD_TIMEOUT_S = 3600


@dataclasses.dataclass(frozen=True)
class FleetCell:
    """One declarative matrix point. `gate=True` marks the D=1 /
    random-partitioner lane whose rows become exp6's results + gate."""
    kind: str                    # "tec" | "identity"
    scale: str
    scenario: str
    partitioner: str = "random"
    repartition_every: int = 0
    n_devices: int = 1
    seeds: tuple = (0,)
    gate: bool = False

    @property
    def name(self) -> str:
        return (f"{self.kind}:{self.scenario}:{self.partitioner}"
                f":d{self.n_devices}")

    def payload(self) -> dict:
        return dict(dataclasses.asdict(self), seeds=list(self.seeds))


def build_matrix(scale: str, n_rep: int) -> list:
    """The quick/full fleet matrix.

    * gate lane: every scenario x random partitioner x D=1, full
      replica set (exp6's historical sweep, now one cell each);
    * partitioner axis: the two workload families under periodic
      voronoi repartitioning (exercises informed repartition + the
      warm-started seeds) — reported, not gated;
    * D axis: the two workload families at 2 and 4 devices as identity
      cells (byte-equality vs the oracle at bench scale).
    """
    from benchmarks import exp6_scenarios as exp6
    seeds = tuple(range(n_rep))
    cells = [FleetCell("tec", scale, s, seeds=seeds, gate=True)
             for s in exp6.SCENARIOS]
    cells += [FleetCell("tec", scale, s, partitioner="voronoi",
                        repartition_every=50,
                        seeds=seeds[:max(2, n_rep // 2)])
              for s in exp6.WORKLOAD_SCENARIOS]
    cells += [FleetCell("identity", scale, s, n_devices=d, seeds=(7,))
              for s in exp6.WORKLOAD_SCENARIOS for d in (2, 4)]
    return cells


# ---------------------------------------------------------------------------
# Child side: one cell -> one RESULT dict
# ---------------------------------------------------------------------------


def run_cell_payload(payload: dict) -> dict:
    """Execute one cell in THIS process (the subprocess entrypoint; also
    callable inline for tests). Returns the cell's RESULT dict."""
    kind, scale = payload["kind"], payload["scale"]
    scen, seeds = payload["scenario"], list(payload["seeds"])
    meta = {"cell": f"{kind}:{scen}:{payload['partitioner']}"
                    f":d{payload['n_devices']}",
            "kind": kind, "scenario": scen, "scale": scale,
            "partitioner": payload["partitioner"],
            "repartition_every": payload["repartition_every"],
            "n_devices": payload["n_devices"], "seeds": seeds,
            "gate": bool(payload.get("gate"))}
    from benchmarks import exp6_scenarios as exp6
    if kind == "tec":
        row = exp6.run_cell(scale, scen, seeds,
                            partitioner=payload["partitioner"],
                            repartition_every=payload["repartition_every"])
        return dict(meta, row=row)
    if kind != "identity":
        raise ValueError(f"unknown cell kind {kind!r}")

    import jax
    import numpy as np
    from repro.core.engine import run
    cfg = dataclasses.replace(
        exp6.scenario_cfg(scale, scen, gaia=True),
        timesteps=IDENTITY_TIMESTEPS[scale])
    t0 = time.time()
    st0, s0, c0 = run(jax.random.key(seeds[0]), cfg)
    st1, s1, c1 = run(jax.random.key(seeds[0]), dataclasses.replace(
        cfg, sharding="lp_device", n_devices=payload["n_devices"]))
    mismatch = [k for k in IDENTITY_STATE_KEYS
                if not np.array_equal(np.asarray(st0[k]),
                                      np.asarray(st1[k]))]
    mismatch += [f"series:{k}" for k in IDENTITY_SERIES_KEYS
                 if not np.array_equal(np.asarray(s0[k]),
                                       np.asarray(s1[k]))]
    return dict(meta, match=not mismatch, mismatch=mismatch,
                shard_overflow=float(c1["shard_overflow"]),
                mean_lcr=round(float(c1["mean_lcr"]), 4),
                migrations=float(c1["migrations"]),
                timesteps=cfg.timesteps,
                wall_s=round(time.time() - t0, 1))


# ---------------------------------------------------------------------------
# Executors: how a cell becomes a process
# ---------------------------------------------------------------------------


class Executor:
    """Scheduler/launcher interface. `run` maps cells to their RESULT
    dicts, order-preserving; a cell whose process fails raises (the
    fleet is exact-or-loud, like every gate in this repo)."""

    kind = "abstract"

    def run(self, cells: list) -> list:
        raise NotImplementedError


class LocalExecutor(Executor):
    """One subprocess per cell on this host, at most `workers` alive at
    once. Each child gets its own XLA runtime with the cell's forced
    host-device count — the only way to vary the device mesh per cell,
    since a process's device count is fixed at first jax import. The
    children are CPU processes by design: on an accelerator this process
    holds the chips, and `run` refuses."""

    kind = "local"

    def __init__(self, workers: int | None = None):
        self.workers = int(workers or max(1, (os.cpu_count() or 1) // 2))

    def _launch(self, cell: FleetCell):
        env = dict(
            os.environ,
            PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""),
            XLA_FLAGS="--xla_force_host_platform_device_count="
                      f"{max(cell.n_devices, 1)}",
            XLA_PYTHON_CLIENT_PREALLOCATE="false",
        )
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--cell", json.dumps(cell.payload())],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env)

    def run(self, cells: list) -> list:
        from benchmarks.common import on_accelerator
        if on_accelerator():
            raise RuntimeError(
                "the local fleet executor starts one process per cell with "
                "its own forced host-device count, but this process holds "
                "the accelerator, so a child would fail or hang. Run the "
                "fleet on the CPU (JAX_PLATFORMS=cpu)")
        pending = list(enumerate(cells))
        live: dict = {}  # index -> (cell, proc, t0)
        results: list = [None] * len(cells)
        deadline = time.time() + CHILD_TIMEOUT_S
        while pending or live:
            while pending and len(live) < self.workers:
                i, cell = pending.pop(0)
                live[i] = (cell, self._launch(cell), time.time())
                print(f"[fleet] launch {cell.name} "
                      f"({len(live)} live, {len(pending)} queued)",
                      flush=True)
            if time.time() > deadline:
                for _, p, _ in live.values():
                    p.kill()
                raise TimeoutError(
                    f"fleet exceeded {CHILD_TIMEOUT_S}s with "
                    f"{len(live)} cells still running")
            time.sleep(0.2)
            for i in [i for i, (_, p, _) in live.items()
                      if p.poll() is not None]:
                cell, proc, t0 = live.pop(i)
                out, err = proc.communicate()
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"cell {cell.name} failed "
                        f"(rc={proc.returncode}):\n{out}{err}")
                results[i] = parse_result(out, cell.name)
                print(f"[fleet] done   {cell.name} "
                      f"({time.time() - t0:.0f}s)", flush=True)
        return results


class ContainerExecutor(Executor):
    """Launch each cell in an OCI container (one image, one cell per
    container, host networking for the result stream). Declared so
    fleet definitions can already target it; wiring needs a container
    runtime on the bench host."""

    kind = "container"

    def __init__(self, image: str = "repro-bench:latest"):
        self.image = image

    def run(self, cells: list) -> list:
        raise NotImplementedError(
            "container executor: no container runtime is wired up yet — "
            "use --executor local (the cell protocol is identical)")


class K8sExecutor(Executor):
    """Submit each cell as a Kubernetes Job and collect RESULT lines
    from the pod logs. Same declarative cells, cluster-scale fan-out."""

    kind = "k8s"

    def __init__(self, namespace: str = "bench"):
        self.namespace = namespace

    def run(self, cells: list) -> list:
        raise NotImplementedError(
            "k8s executor: no cluster credentials are wired up yet — "
            "use --executor local (the cell protocol is identical)")


EXECUTORS = {"local": LocalExecutor, "container": ContainerExecutor,
             "k8s": K8sExecutor}


def parse_result(stdout: str, name: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"cell {name}: no RESULT line in {stdout!r}")


# ---------------------------------------------------------------------------
# Merge + gate
# ---------------------------------------------------------------------------


def merge(cells: list, results: list, scale: str, n_rep: int) -> dict:
    """Fold cell RESULTs into the BENCH_scenarios.json document: gate
    cells become exp6's results rows (schema-identical to a sequential
    exp6 run); everything else lands under "fleet" and the identity
    cells are asserted byte-equal right here."""
    from benchmarks import exp6_scenarios as exp6
    gate_rows = [r["row"] for c, r in zip(cells, results) if c.gate]
    fleet = {
        "executor": "local",
        "cells": [{k: v for k, v in r.items() if k != "row"}
                  for r in results],
        "extra_tec": [r["row"] for c, r in zip(cells, results)
                      if c.kind == "tec" and not c.gate],
        "identity": [r for c, r in zip(cells, results)
                     if c.kind == "identity"],
    }
    for r in fleet["identity"]:
        assert r["shard_overflow"] == 0.0, \
            f"{r['cell']}: shard overflow at bench scale"
        assert r["match"], \
            f"{r['cell']}: sharded run diverged from oracle on " \
            f"{r['mismatch']}"
    return exp6.assemble(gate_rows, scale, n_rep, fleet=fleet)


def main(scale: str = "quick", replicas=None, executor: str = "local",
         workers: int | None = None):
    from benchmarks import exp6_scenarios as exp6
    from benchmarks.common import default_replicas
    n_rep = default_replicas(scale, replicas)
    cells = build_matrix(scale, n_rep)
    print(f"[fleet] {len(cells)} cells ({scale}, n={n_rep}) on "
          f"executor={executor}")
    t0 = time.time()
    results = EXECUTORS[executor](workers) if executor == "local" \
        else EXECUTORS[executor]()
    results = results.run(cells)
    doc = merge(cells, results, scale, n_rep)
    doc["fleet"]["wall_s"] = round(time.time() - t0, 1)
    for row in doc["results"]:
        exp6.print_row(row)
    for r in doc["fleet"]["identity"]:
        print(f"[fleet] {r['cell']:24s} identity OK "
              f"(lcr {r['mean_lcr']}, {r['wall_s']}s)")
    return exp6.write_and_gate(doc)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scale", nargs="?", default="quick",
                    choices=["quick", "full"])
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--executor", default="local",
                    choices=sorted(EXECUTORS))
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--cell", default=None,
                    help="(internal) run one cell payload and print "
                         "its RESULT line")
    a = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    if a.cell is not None:
        print("RESULT " + json.dumps(run_cell_payload(json.loads(a.cell))))
    else:
        print(f"[fleet] compile cache: {cache_dir}")
        main(a.scale, a.replicas, a.executor, a.workers)
