"""The control: the reference itself, computing positions in bfloat16
(the precision below the configuration's float32), put in the program's
place. The comparison must call it not correct."""
import jax.numpy as jnp
import pytest

from chipbench.sut import ReferenceSUT
from chipbench.tests.tiny import cells, run_cell, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", [c for c in cells() if "-d4" not in c])
def test_bfloat16_control_is_not_correct(root, cell):
    res = run_cell(root, cell, sut_factory=lambda c, w, s: ReferenceSUT(
        c, w, s, jnp.bfloat16))
    assert res["correct"] is False
    assert res["checks"]["state_rows"]["value"] > 0


@pytest.mark.parametrize("cell", [c for c in cells() if "-d4" not in c])
def test_float32_reference_in_the_programs_place_is_correct(root, cell):
    res = run_cell(root, cell, sut_factory=lambda c, w, s: ReferenceSUT(
        c, w, s, jnp.float32))
    assert res["correct"] is True
