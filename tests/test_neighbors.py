"""Backend parity for the proximity hot spot (tentpole contract).

Every `proximity_backend` must produce BIT-IDENTICAL counts to the dense
jnp oracle — the engine's transparency invariant (§4.2) extends to the
neighbor-search implementation: switching backends may change the speed,
never the simulation. Cases deliberately include agents straddling the
torus seam, a range larger than the grid cell side, worlds too small to
tessellate (dense fallback), and clustered (non-uniform) positions that
stress the fixed per-cell capacity.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import neighbors
from repro.core.abm import (ABMConfig, PROXIMITY_BACKENDS, _dense_counts,
                            interaction_counts)
from repro.core.engine import EngineConfig, run
from repro.core.heuristics import HeuristicConfig

BACKENDS = [b for b in PROXIMITY_BACKENDS if b != "dense"]


def _case(seed, n, n_lp, area, rng, seam=False):
    k = jax.random.key(seed)
    pos = jax.random.uniform(jax.random.fold_in(k, 0), (n, 2), maxval=area)
    if seam:
        # band of width area/10 straddling the wrap line on both axes
        pos = (pos * 0.1 - area * 0.05) % area
    lp = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, n_lp)
    sender = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.4, (n,))
    return pos, lp, sender


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n,n_lp,area,rng,seam", [
    (200, 4, 1000.0, 80.0, False),
    pytest.param(300, 3, 1000.0, 60.0, True,
                 marks=pytest.mark.slow),  # seam cluster, odd N (nightly)
    pytest.param(128, 8, 500.0, 90.0, False, marks=pytest.mark.slow),
    (96, 2, 100.0, 45.0, False),  # area/rng < 3: dense fallback path
    (150, 4, 300.0, 40.0, True),  # seam + ncell >= 3
    (64, 3, 1000.0, 400.0, False),  # range > cell side forces ncell=2 -> dense
])
def test_backend_parity_bit_identical(backend, n, n_lp, area, rng, seam):
    pos, lp, sender = _case(n + int(seam), n, n_lp, area, rng, seam)
    # seam cases pile every SE into ~1% of the area: give the grid an
    # overflow-proof capacity there (auto capacity assumes ~uniform
    # density; its adequacy is what the uniform cases exercise)
    cfg = ABMConfig(n_se=n, n_lp=n_lp, area=area, interaction_range=rng,
                    grid_capacity=n if seam else 0)
    ref = _dense_counts(pos, lp, sender, cfg)
    got = interaction_counts(
        pos, lp, sender, dataclasses.replace(cfg, proximity_backend=backend))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("backend", ["grid", "pallas_grid"])
def test_parity_under_clustering_with_explicit_capacity(backend):
    """All SEs piled into one corner cell: auto capacity would overflow,
    but an explicit grid_capacity=n keeps the grid exact."""
    n, area, rng = 120, 1000.0, 100.0
    k = jax.random.key(11)
    pos = jax.random.uniform(k, (n, 2), maxval=40.0)  # one cell's worth
    lp = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, 4)
    sender = jnp.ones((n,), bool)
    cfg = ABMConfig(n_se=n, n_lp=4, area=area, interaction_range=rng)
    ref = _dense_counts(pos, lp, sender, cfg)
    got = interaction_counts(pos, lp, sender, dataclasses.replace(
        cfg, proximity_backend=backend, grid_capacity=n))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_overflow_path_on_hotspot_distribution():
    """The satellite contract for clustered inputs: on a hotspot
    distribution the *uniform* auto-capacity overflows (flag raised, and
    the resulting counts really are undercounted — the failure is not
    hypothetical), while an explicit ABMConfig.grid_capacity override
    restores exact parity with the dense oracle. The mobility-aware
    auto-capacity must also hold on its own."""
    from repro.core.abm import init_abm

    cfg = ABMConfig(n_se=300, n_lp=4, area=2000.0, interaction_range=100.0,
                    mobility="hotspot", n_groups=3, group_radius=100.0)
    st = init_abm(jax.random.key(2), cfg)
    pos, lp = st["pos"], st["lp"]
    sender = jnp.ones((cfg.n_se,), bool)

    # uniform-density capacity (what RWP would use): overflows on blobs
    uniform_spec = neighbors.make_grid_spec(cfg.n_se, cfg.area,
                                            cfg.interaction_range)
    assert bool(neighbors.build_grid(pos, uniform_spec)["overflow"])
    under = neighbors.grid_lp_counts(pos, lp, sender, cfg.n_lp, cfg.area,
                                     cfg.interaction_range, uniform_spec)
    ref = _dense_counts(pos, lp, sender, cfg)
    assert int(np.asarray(under).sum()) < int(np.asarray(ref).sum())

    # the clustered auto-capacity holds, and explicit override is exact
    assert not bool(neighbors.build_grid(pos, cfg.grid_spec())["overflow"])
    got_auto = interaction_counts(pos, lp, sender, cfg)
    np.testing.assert_array_equal(np.asarray(got_auto), np.asarray(ref))
    got = interaction_counts(pos, lp, sender,
                             dataclasses.replace(cfg, grid_capacity=cfg.n_se))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_grid_spec_geometry():
    spec = neighbors.make_grid_spec(10_000, 10_000.0, 250.0)
    assert spec.ncell == 40 and spec.cell >= 250.0
    # too small to tessellate -> None (callers go dense)
    assert neighbors.make_grid_spec(100, 100.0, 40.0) is None
    assert neighbors.make_grid_spec(100, 300.0, 150.0) is None
    # explicit capacity wins over the density heuristic
    assert neighbors.make_grid_spec(1000, 1000.0, 100.0, capacity=7).capacity == 7


def test_build_grid_overflow_flag():
    n, area = 64, 1000.0
    pos = jnp.full((n, 2), 5.0)  # everyone in cell (0, 0)
    tight = neighbors.GridSpec(ncell=10, cell=100.0, capacity=8)
    roomy = neighbors.GridSpec(ncell=10, cell=100.0, capacity=64)
    assert bool(neighbors.build_grid(pos, tight)["overflow"])
    assert not bool(neighbors.build_grid(pos, roomy)["overflow"])


def test_build_grid_layout():
    k = jax.random.key(3)
    pos = jax.random.uniform(k, (200, 2), maxval=1000.0)
    spec = neighbors.make_grid_spec(200, 1000.0, 100.0)
    g = neighbors.build_grid(pos, spec)
    counts = np.asarray(g["counts"])
    assert counts.sum() == 200
    # member table agrees with the per-cell counts and holds each SE once
    table = np.asarray(g["table"])
    members = table[table >= 0]
    assert sorted(members.tolist()) == list(range(200))
    for c in range(spec.ncell ** 2):
        assert (table[c] >= 0).sum() == counts[c]


def test_dense_chunked_matches_oracle():
    pos, lp, sender = _case(5, 230, 4, 1000.0, 120.0)
    cfg = ABMConfig(n_se=230, n_lp=4, area=1000.0, interaction_range=120.0)
    ref = _dense_counts(pos, lp, sender, cfg)
    got = neighbors.dense_lp_counts_chunked(pos, lp, sender, 4, 1000.0,
                                            120.0, chunk=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_engine_evolution_identical_across_backends():
    """Full engine runs (scan + self-clustering) must be bit-identical
    under backend switch — speed knobs never touch the simulation."""
    results = {}
    for backend in ("dense", "grid"):
        abm = ABMConfig(n_se=120, n_lp=4, area=1000.0, speed=5.0,
                        interaction_range=80.0, p_interact=0.3,
                        proximity_backend=backend)
        cfg = EngineConfig(abm=abm, heuristic=HeuristicConfig(mf=1.2, mt=5),
                           gaia_on=True, timesteps=50)
        st, series, _ = run(jax.random.key(7), cfg)
        results[backend] = (st, series)
    st_d, series_d = results["dense"]
    st_g, series_g = results["grid"]
    np.testing.assert_array_equal(np.asarray(st_d["pos"]),
                                  np.asarray(st_g["pos"]))
    np.testing.assert_array_equal(np.asarray(st_d["lp"]),
                                  np.asarray(st_g["lp"]))
    for k in ("local_msgs", "remote_msgs", "migrations"):
        np.testing.assert_array_equal(np.asarray(series_d[k]),
                                      np.asarray(series_g[k]))


def test_use_pallas_removed_fails_loudly():
    # the PR-4 shim era is over: stale call sites must fail with a
    # message naming the replacement knob, not silently ignore the flag
    with pytest.raises(TypeError, match="proximity_backend"):
        ABMConfig(n_se=64, n_lp=2, area=500.0, interaction_range=100.0,
                  use_pallas=True)


def test_invalid_backend_rejected():
    with pytest.raises(ValueError):
        ABMConfig(proximity_backend="voronoi")


# ---------------------------------------------------------------------------
# CSR candidate path (million-SE tier): bit-identity under any memory budget
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("budget_entries", [1, 37, 4096, 26_000])
def test_csr_chunk_budget_bit_identical(budget_entries):
    """The lax.map chunk size is a pure memory knob: any budget — down to
    one candidate entry (one cell row per chunk, 12 sequential chunks),
    or 5 cell rows per chunk with the last chunk padded — must reproduce
    the dense oracle bit-for-bit."""
    n, n_lp, area, rng = 200, 4, 1000.0, 80.0
    pos, lp, sender = _case(9, n, n_lp, area, rng)
    cfg = ABMConfig(n_se=n, n_lp=n_lp, area=area, interaction_range=rng)
    ref = _dense_counts(pos, lp, sender, cfg)
    spec = cfg.grid_spec()
    got = neighbors.grid_lp_counts(pos, lp, sender, n_lp, area, rng, spec,
                                   budget_entries=budget_entries)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("mobility", ["hotspot", "group", "flock"])
def test_csr_parity_across_mobility_models(mobility):
    """Property contract for the sparse candidate path: on every mobility
    model's (clustered, non-uniform) initial layout, the CSR sweep with
    the mobility-aware auto capacity is bit-identical to the dense
    oracle."""
    from repro.core.abm import init_abm

    cfg = ABMConfig(n_se=256, n_lp=4, area=2000.0, interaction_range=100.0,
                    mobility=mobility, n_groups=4, group_radius=150.0)
    st = init_abm(jax.random.key(17), cfg)
    pos, lp = st["pos"], st["lp"]
    sender = jnp.ones((cfg.n_se,), bool)
    ref = _dense_counts(pos, lp, sender, cfg)
    got = interaction_counts(pos, lp, sender, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_csr_overflow_drop_set_matches_table_oracle():
    """Adversarial layout that overflows the uniform capacity: the CSR
    sweep must drop EXACTLY the members the padded candidate-table
    oracle drops (both keep the first `capacity` members of each cell in
    sorted-id order), so even the overflowed counts — not just the flag —
    are bit-identical across representations."""
    n, n_lp, area, rng = 240, 4, 1000.0, 100.0
    k = jax.random.key(21)
    # three tight blobs -> uniform capacity is guaranteed to overflow
    centers = jnp.array([[100.0, 100.0], [500.0, 900.0], [900.0, 400.0]])
    pos = (centers[jnp.arange(n) % 3]
           + jax.random.normal(k, (n, 2)) * 15.0) % area
    lp = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, n_lp)
    sender = jnp.ones((n,), bool)
    spec = neighbors.make_grid_spec(n, area, rng)
    assert bool(neighbors.build_grid(pos, spec)["overflow"])

    cand, _ = neighbors.candidate_table(pos, spec)
    idx = jnp.arange(n, dtype=jnp.int32)
    table_counts = neighbors.rows_counts_chunked(
        pos, lp, n_lp, area, rng, pos, idx, sender, cand)
    csr_counts = neighbors.grid_lp_counts(pos, lp, sender, n_lp, area, rng,
                                          spec)
    np.testing.assert_array_equal(np.asarray(csr_counts),
                                  np.asarray(table_counts))
    # and both really are undercounts (the overflow is not hypothetical)
    cfg = ABMConfig(n_se=n, n_lp=n_lp, area=area, interaction_range=rng)
    ref = _dense_counts(pos, lp, sender, cfg)
    assert int(np.asarray(csr_counts).sum()) < int(np.asarray(ref).sum())


def test_mem_budget_clamp_is_loud():
    """A hard memory budget may shrink the per-cell capacity below what
    a clustered layout needs — the contract is exact-or-loud: the clamp
    must trip the overflow flag, never silently undercount."""
    from repro.core.abm import init_abm, interaction_counts_overflow

    cfg = ABMConfig(n_se=1024, n_lp=4, area=4000.0, interaction_range=100.0,
                    mobility="hotspot", n_groups=1, group_radius=100.0,
                    mem_budget_mb=1)
    spec = cfg.grid_spec()
    unclamped = dataclasses.replace(cfg, mem_budget_mb=0).grid_spec()
    assert spec.capacity == neighbors.budget_capacity(spec.ncell, 1)
    assert spec.capacity < unclamped.capacity
    st = init_abm(jax.random.key(4), cfg)
    sender = jnp.ones((cfg.n_se,), bool)
    _, overflow = interaction_counts_overflow(st["pos"], st["lp"], sender,
                                              cfg)
    assert bool(overflow)
    # the unclamped (budget-free) spec is exact on the same layout
    assert not bool(neighbors.build_grid(st["pos"], unclamped)["overflow"])


def test_generous_budget_leaves_simulation_bit_identical():
    """mem_budget_mb is a speed/memory knob, never a simulation knob: a
    budget roomy enough not to clamp capacity must give bit-identical
    engine trajectories (chunk boundaries move; counts must not)."""
    abm = ABMConfig(n_se=150, n_lp=4, area=1000.0, speed=5.0,
                    interaction_range=80.0, p_interact=0.3)
    base = EngineConfig(abm=abm, heuristic=HeuristicConfig(mf=1.2, mt=5),
                        gaia_on=True, timesteps=25)
    st0, s0, _ = run(jax.random.key(13), base)
    st1, s1, _ = run(jax.random.key(13),
                     dataclasses.replace(base, mem_budget_mb=256))
    np.testing.assert_array_equal(np.asarray(st0["pos"]),
                                  np.asarray(st1["pos"]))
    np.testing.assert_array_equal(np.asarray(st0["lp"]),
                                  np.asarray(st1["lp"]))
    for k in ("local_msgs", "remote_msgs", "migrations"):
        np.testing.assert_array_equal(np.asarray(s0[k]), np.asarray(s1[k]))


def test_budget_helpers():
    # 0 = unlimited -> the fixed default chunk budget
    assert neighbors.chunk_entries(0) == neighbors._CHUNK_BUDGET
    # 1 MB / 20 bytes per candidate entry, floored at 4096 entries
    assert neighbors.chunk_entries(1) == (1 << 20) // 20
    assert neighbors.chunk_entries(-5) == neighbors._CHUNK_BUDGET
    # capacity budget is monotone in the budget and never below 1
    caps = [neighbors.budget_capacity(400, mb) for mb in (1, 8, 64)]
    assert caps == sorted(caps) and caps[0] >= 1


def test_engine_budget_propagates_to_abm():
    abm = ABMConfig(n_se=64, n_lp=2, area=500.0, interaction_range=100.0)
    cfg = EngineConfig(abm=abm, heuristic=HeuristicConfig(),
                       mem_budget_mb=64)
    assert cfg.abm.mem_budget_mb == 64
    # an explicit per-ABM budget is not overridden by the engine knob
    abm2 = dataclasses.replace(abm, mem_budget_mb=8)
    cfg2 = EngineConfig(abm=abm2, heuristic=HeuristicConfig(),
                        mem_budget_mb=64)
    assert cfg2.abm.mem_budget_mb == 8


# ---------------------------------------------------------------------------
# Cell-slab sweep (all-rows sweeps): the dense oracle and the row walk agree
# ---------------------------------------------------------------------------


def _row_walk(pos, lp, sender, n_lp, area, rng, spec, valid=None):
    """`rows_grid_counts` with every agent a row, in id order."""
    grid = neighbors.build_grid(pos, spec, valid=valid, with_table=False)
    idx = jnp.arange(pos.shape[0], dtype=jnp.int32)
    return neighbors.rows_grid_counts(pos, lp, n_lp, area, rng, spec, grid,
                                      pos, idx, sender)


def _slab_case(name):
    """(pos, labels, sender, n_lp, area, rng, spec, valid, exact)."""
    k = jax.random.key(31)
    if name == "uniform_seam":
        n, n_lp, area, rng = 400, 4, 1000.0, 60.0
        pos, lp, sender = _case(41, n, n_lp, area, rng)
        # a third of the agents on the wrap lines of both axes
        seam = (pos * 0.04 - area * 0.02) % area
        pos = jnp.where((jnp.arange(n) % 3 == 0)[:, None], seam, pos)
        return (pos, lp, sender, n_lp, area, rng,
                neighbors.make_grid_spec(n, area, rng, capacity=n), None,
                True)
    if name == "ncell3":
        n, n_lp, area, rng = 90, 3, 300.0, 100.0
        pos, lp, sender = _case(42, n, n_lp, area, rng)
        spec = neighbors.make_grid_spec(n, area, rng)
        assert spec.ncell == 3
        return pos, lp, sender, n_lp, area, rng, spec, None, True
    if name == "clustered_explicit_capacity":
        n, n_lp, area, rng = 150, 4, 1000.0, 100.0
        pos = jax.random.uniform(k, (n, 2), maxval=40.0)  # one corner cell
        lp = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, n_lp)
        sender = jnp.ones((n,), bool)
        return (pos, lp, sender, n_lp, area, rng,
                neighbors.make_grid_spec(n, area, rng, capacity=n), None,
                True)
    if name == "overflow":
        # the layout of test_csr_overflow_drop_set_matches_table_oracle
        n, n_lp, area, rng = 240, 4, 1000.0, 100.0
        k = jax.random.key(21)
        centers = jnp.array([[100.0, 100.0], [500.0, 900.0],
                             [900.0, 400.0]])
        pos = (centers[jnp.arange(n) % 3]
               + jax.random.normal(k, (n, 2)) * 15.0) % area
        lp = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, n_lp)
        sender = jnp.ones((n,), bool)
        return (pos, lp, sender, n_lp, area, rng,
                neighbors.make_grid_spec(n, area, rng), None, False)
    if name == "open_world_dead_rows":
        n, n_lp, area, rng = 300, 4, 1000.0, 80.0
        pos, lp, sender = _case(43, n, n_lp, area, rng)
        valid = jax.random.bernoulli(jax.random.fold_in(k, 5), 0.7, (n,))
        # dead rows: lp -1 and never senders, as the open-world engine
        # keeps them; a cramped capacity that only the live rows fit
        lp = jnp.where(valid, lp, -1)
        spec = neighbors.make_grid_spec(n, area, rng)
        live_max = int(np.bincount(np.asarray(
            neighbors.cell_ids(pos, spec))[np.asarray(valid)]).max())
        spec = dataclasses.replace(spec, capacity=live_max)
        return pos, lp, sender & valid, n_lp, area, rng, spec, valid, True
    if name == "epidemic_two_labels":
        n, area, rng = 300, 1000.0, 80.0
        pos, _, query = _case(44, n, 2, area, rng)
        valid = jax.random.bernoulli(jax.random.fold_in(k, 6), 0.8, (n,))
        infectious = jax.random.bernoulli(jax.random.fold_in(k, 7), 0.3,
                                          (n,))
        labels = jnp.where(valid, infectious.astype(jnp.int32), -1)
        return (pos, labels, query & valid, 2, area, rng,
                neighbors.make_grid_spec(n, area, rng), valid, True)
    raise ValueError(name)


@pytest.mark.parametrize("name", [
    "uniform_seam", "ncell3", "clustered_explicit_capacity", "overflow",
    "open_world_dead_rows", "epidemic_two_labels"])
def test_slab_sweep_matches_dense_and_row_walk(name):
    """The cell-slab sweep equals the row walk over all rows, bit for
    bit, and the dense oracle wherever no cell overflows; under
    overflow it drops exactly the members `candidate_table` drops (and
    still counts for the rows ranked past capacity)."""
    pos, lp, sender, n_lp, area, rng, spec, valid, exact = _slab_case(name)
    got, overflow = neighbors.slab_lp_counts(pos, lp, sender, n_lp, area,
                                             rng, spec, valid)
    got = np.asarray(got)
    assert bool(overflow) == (not exact)
    walk = _row_walk(pos, lp, sender, n_lp, area, rng, spec, valid)
    np.testing.assert_array_equal(got, np.asarray(walk))
    ref = np.asarray(neighbors.dense_lp_counts(pos, lp, sender, n_lp, area,
                                               rng))
    if exact:
        np.testing.assert_array_equal(got, ref)
    else:
        cand, _ = neighbors.candidate_table(pos, spec)
        table = neighbors.rows_counts_chunked(
            pos, lp, n_lp, area, rng, pos,
            jnp.arange(pos.shape[0], dtype=jnp.int32), sender, cand)
        np.testing.assert_array_equal(got, np.asarray(table))
        assert got.sum() < ref.sum()
    if name == "epidemic_two_labels":
        from repro.core.abm import epidemic_exposure_overflow
        cfg = ABMConfig(n_se=pos.shape[0], n_lp=4, area=area,
                        interaction_range=rng, workload="epidemic")
        assert cfg.grid_spec() == spec
        exposure, ovf = epidemic_exposure_overflow(pos, lp, sender, cfg,
                                                   valid=valid)
        np.testing.assert_array_equal(np.asarray(exposure), ref[:, 1])
        assert not bool(ovf)


def _wide_gathers(hlo: str, n: int) -> list:
    """Gathers of the HLO text that fetch more than `n` index rows: the
    product of the output dims that are not slice (offset) dims."""
    wide = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* gather\("
                      r".*offset_dims=\{([\d,]*)\}", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        offset = {int(d) for d in m.group(2).split(",") if d}
        rows = math.prod(d for i, d in enumerate(dims) if i not in offset)
        if rows > n:
            wide.append(line.strip()[:160])
    return wide


def test_grid_sweep_has_no_wide_gather():
    """The all-rows grid sweep gathers nothing per candidate slot: no
    gather of the lowered `interaction_counts_overflow` fetches more
    than N index rows (the row walk, for contrast, fetches N x capacity
    per neighbour cell)."""
    from repro.core.abm import interaction_counts_overflow

    n = 600
    cfg = ABMConfig(n_se=n, n_lp=4, area=2000.0, interaction_range=100.0)
    spec = cfg.grid_spec()
    assert spec.ncell ** 2 < n
    args = (jax.ShapeDtypeStruct((n, 2), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_))

    def hlo(fn):
        return jax.jit(fn).lower(*args).as_text(dialect="hlo")

    assert _wide_gathers(hlo(lambda p, l, s: interaction_counts_overflow(
        p, l, s, cfg)), n) == []
    assert _wide_gathers(hlo(lambda p, l, s: _row_walk(
        p, l, s, cfg.n_lp, cfg.area, cfg.interaction_range, spec)), n)


def test_slab_walk_counts_padded_cells():
    """`slab_walk`'s slot count is cell rows x (ncell + 2) positions (the
    two wrapped halo cells of each row included) x 9 x capacity^2,
    padded to whole chunks of cell rows, and is what `abm.walk_slots`
    reports."""
    from repro.core.abm import walk_slots

    assert neighbors.slab_walk(40, 35) == (40, 1, 40 * 42 * 9 * 35 * 35)
    # one cell row of 14 x 19^2 slot pairs per chunk, 12 chunks
    assert neighbors.slab_walk(12, 19, 1)[:2] == (1, 12)
    # 4 rows per chunk: 3 chunks, no padding
    assert neighbors.slab_walk(12, 19, 22_000) == (4, 3,
                                                   12 * 14 * 9 * 19 * 19)
    # 5 rows per chunk: 3 chunks, the last padded by 3 empty cell rows
    assert neighbors.slab_walk(12, 19, 26_000) == (5, 3,
                                                   15 * 14 * 9 * 19 * 19)
    cfg = ABMConfig(n_se=10_000, n_lp=4, area=10_000.0,
                    interaction_range=250.0)
    assert walk_slots(cfg) == 18_522_000
