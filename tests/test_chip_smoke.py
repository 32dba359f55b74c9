"""chip_smoke.py: no CPU fallback, and a comparison that names what
differs."""
import os
import subprocess
import sys

import numpy as np

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_mismatches_name_each_key_with_its_largest_difference():
    a = {"pos": np.array([[1.0, 2.0]], np.float32),
         "lp": np.array([0, 1], np.int32),
         "t": np.array(5, np.int32)}
    b = {"pos": np.array([[1.0, 2.5]], np.float32),
         "lp": np.array([0, 1, 2], np.int32),
         "t": np.array(5, np.int32)}
    assert chip_smoke.mismatches(a, b, a) == {"pos": 0.5, "lp": float("inf")}
