"""Every cell of BENCHMARK.json, driven end to end at a tiny size on the
CPU: the traffic file, the system under test, the reference check and
the end-to-end metric readers."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.tests.tiny import ROOT, bench, cells, run_cell, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", cells())
def test_cell_runs_and_is_correct(root, cell):
    res = run_cell(root, cell)
    assert res["correct"] is True, res["stderr"][-2000:]
    want = {m["name"] for m in bench(root)["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    keys = [k for k in res if k != "stderr"]
    assert keys[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert "compiles in the window: 0" in res["stderr"]


def test_every_reader_exists(root):
    b = bench(root)
    for m in b["end_to_end"]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "e2e",
                                           m["name"] + ".py"))
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py"))


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "paper10k.rwp-gaia", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run_py(ROOT)
    assert p.returncode == 3
    assert p.stdout == ""


def test_without_the_program_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("cell", cells())
def test_traced_run_reads_the_host_span_metrics(root, cell):
    res = run_cell(root, cell, trace=True)
    assert res["correct"] is True
    want = {m["name"] for m in bench(root)["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["source"] in ("host_clock", "program_counter")}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_traced_run_without_step_scopes_gives_no_result(root, monkeypatch):
    """A traced run on a device whose compiled programs carry no
    `step.<phase>` scope (a scope renamed, or JAX's compile hook moved)
    fails instead of leaving the phase metrics out of its line."""
    import contextlib
    import gzip
    import io
    import time

    from chipbench import harness
    from chipbench import trace as tr
    with gzip.open(os.path.join(ROOT, "chipbench", "tests", "data",
                                "small.xplane.pb.gz")) as f:
        data = f.read()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, ".jax_cache"))
    monkeypatch.setattr(tr.HloCapture, "scopes", lambda self: {})
    monkeypatch.setattr(tr.Trace, "from_file", classmethod(
        lambda cls, path, scopes: cls.from_bytes(data, scopes)))
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = harness.run(root, "paper10k.rwp-gaia", 2**31 + 9, 0.5, True,
                         time.perf_counter(), skip_device_check=True)
    assert rc == harness.TRACE_BLIND
    assert out.getvalue() == ""
    assert "no step.<phase> scope" in err.getvalue()
