"""A copy of the benchmark's files at a size the CPU can run in
seconds, and a driver that runs one cell there without a chip."""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the paper's density (1e-4 SE per unit^2) on a smaller torus
TINY_ABM = {"n_se": 400, "area": 2000.0}
TINY_MIX = {"n_active": 320, "depart": 16, "arrive": 16,
            "query_neighbors": 4}


#: the four-chip cell, rehearsed here on forced host devices until it is
#: measured on the chip and enters BENCHMARK.json (PERF.md, Open questions)
D4_CELL = {"name": "paper10k-d4.rwp-gaia", "config": "gaia-paper-10k-d4",
           "traffic": "rwp-gaia", "chips": 4}
D4_CONFIG = {"name": "gaia-paper-10k-d4",
             "file": "chipbench/configs/gaia-paper-10k-d4.json"}
D4_METRICS = {"end_to_end": ["se_steps_per_s"],
              "per_layer": ["phase.proximity_ms_per_step",
                            "phase.heuristic_ms_per_step",
                            "driver.host_gap_ms_per_window",
                            "device.idle_pct",
                            "exchange.collective_ms_per_step",
                            "exchange.bytes_on_wire_per_step"]}
#: sources of the metrics only the four-chip cell reads
D4_SOURCES = {"exchange.collective_ms_per_step": "device_trace",
              "exchange.bytes_on_wire_per_step": "program_counter"}


def _with_d4(bench: dict) -> dict:
    if any(w["name"] == D4_CELL["name"] for w in bench["workloads"]):
        return bench
    bench["workloads"].append(dict(D4_CELL))
    bench["configs"].append(dict(D4_CONFIG))
    for sec, names in D4_METRICS.items():
        have = {m["name"]: m for m in bench[sec]}
        for name in names:
            m = have.get(name)
            if m is None:
                m = {"name": name, "unit": "-",
                     "source": D4_SOURCES[name], "workloads": []}
                bench[sec].append(m)
            m["workloads"] = m.get("workloads", []) + [D4_CELL["name"]]
    return bench


def tiny_root(tmp: str) -> str:
    """A checkout-like directory: BENCHMARK.json (with the four-chip
    cell) and chipbench/ with the configurations and mixes cut to
    TINY_*."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = _with_d4(json.load(f))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(tmp, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cdir = os.path.join(tmp, "chipbench", "configs")
    for f in os.listdir(cdir):
        p = os.path.join(cdir, f)
        with open(p) as fh:
            c = json.load(fh)
        c["engine"]["abm"].update(TINY_ABM)
        with open(p, "w") as fh:
            json.dump(c, fh)
    tdir = os.path.join(tmp, "chipbench", "traffic")
    for f in os.listdir(tdir):
        p = os.path.join(tdir, f)
        with open(p) as fh:
            m = json.load(fh)
        if "n_active" in m.get("world", {}):
            m["world"]["n_active"] = TINY_MIX["n_active"]
        for op in m["round"]:
            if "count" in op:
                op["count"] = TINY_MIX[op["op"]]
        with open(p, "w") as fh:
            json.dump(m, fh)
    return tmp


def cells() -> list:
    """The cells a tiny root holds: BENCHMARK.json's and the four-chip
    one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in _with_d4(json.load(f))["workloads"]]


def bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(root: str, cell: str, seed: int = 2**31 + 5,
             seconds: float = 0.5, trace: bool = False,
             sut_factory=None) -> dict:
    """Run one cell on the CPU; returns the parsed result line."""
    from chipbench import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = harness.run(root, cell, seed, seconds, trace,
                         time.perf_counter(), sut_factory=sut_factory,
                         skip_device_check=True)
    assert rc == 0, err.getvalue()[-2000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    res["stderr"] = err.getvalue()
    return res
