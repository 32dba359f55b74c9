"""The harness, with the timed path broken underneath, must report
`correct` false: once for each fault a cell can have."""
import contextlib

import jax
import jax.numpy as jnp
import pytest

from chipbench.sut import ProgramSUT
from chipbench.tests.tiny import run_cell, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


class Frozen(ProgramSUT):
    """A step that returns its state unchanged (the counters are still
    the real step's)."""

    def step(self, n):
        before = self.eng.state
        c = super().step(n)
        self.eng.state = before
        return c


class HalfMoved(ProgramSUT):
    """Half of the SEs (the odd ids) left out of each step's move."""

    def step(self, n):
        before = self.eng.state
        c = super().step(n)
        st = dict(self.eng.state)
        odd = (jnp.arange(st["pos"].shape[0]) % 2 == 1)[:, None]
        for k in ("pos", "waypoint"):
            st[k] = jnp.where(odd, before[k], st[k])
        self.eng.state = st
        return c


class WrongNeighbours(ProgramSUT):
    """One neighbour list altered where it is produced."""

    def query_neighbors(self, ids):
        ans = super().query_neighbors(ids)
        first = next(iter(ans))
        ans[first] = ans[first][:-1] if ans[first] else [first]
        return ans


class WrongSlot(ProgramSUT):
    """An arrival reported in a slot that was already live."""

    def arrive(self, pos):
        ids = super().arrive(pos)
        return [0] + ids[1:]


@pytest.mark.parametrize("fault,cell", [
    (Frozen, "paper10k.rwp-gaia"),
    (HalfMoved, "paper10k.rwp-gaia"),
    (Frozen, "paper10k.churn-query"),
    (WrongNeighbours, "paper10k.churn-query"),
    (WrongSlot, "paper10k.churn-query"),
    (Frozen, "paper10k-d4.rwp-gaia"),
])
def test_fault_is_not_correct(root, fault, cell):
    res = run_cell(root, cell, sut_factory=fault)
    assert res["correct"] is False


@contextlib.contextmanager
def no_exchange():
    """Every all-to-all between chips delivers padding instead of rows:
    the halo exchange left out."""
    from repro.core.engine import clear_compiled_caches
    orig = jax.lax.all_to_all

    def dropped(x, *a, **k):
        return jnp.full_like(x, -1) if jnp.issubdtype(
            x.dtype, jnp.integer) else jnp.zeros_like(x)

    clear_compiled_caches()
    jax.clear_caches()
    jax.lax.all_to_all = dropped
    try:
        yield
    finally:
        jax.lax.all_to_all = orig
        clear_compiled_caches()
        jax.clear_caches()


def test_missing_exchange_is_not_correct(root):
    with no_exchange():
        res = run_cell(root, "paper10k-d4.rwp-gaia")
    assert res["correct"] is False
