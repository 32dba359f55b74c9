"""Spatial-grid (cell-list) neighbor search on the toroidal square.

The paper's evaluation model (§5.1) is dominated by proximity interaction
matching, which the dense path in `abm.interaction_counts` resolves as an
O(N^2) pairwise sweep. This module provides the standard cell-list fix:
bin SEs into a `ncell x ncell` grid of square cells whose side is at
least `interaction_range`, so every in-range neighbor of an SE lies in
the 3x3 block of cells around it — O(N*k) candidate tests instead of
O(N^2), with k the mean cell occupancy.

Layout (all shapes static so the whole thing JITs and runs under
`lax.scan` inside the engine):

  * SEs are sorted by cell id (`argsort`), giving contiguous per-cell
    segments; `searchsorted` yields per-cell start offsets and counts —
    a CSR layout of the grid (`order` = column indices, `starts` = row
    pointers), and each SE's rank within its cell.
  * Sweeps of EVERY row (`slab_lp_counts`: the engine step, the
    service's `query_lcr`, the epidemic exposure) scatter each live
    SE's x, y and label once into dense per-cell slabs at (rank, cell),
    wrap them by one cell on every side and flatten the cells, minor,
    so the TPU's lanes are full. Each of the 9 neighbour offsets is
    then one static slice of the candidate slabs, and every (receiver
    slot, candidate slot) pair of the two cells is tested; one O(N)
    gather through (rank, cell) brings the counts back to id order.
    Nothing is gathered per candidate slot, which is what the TPU pays
    most for; the price is ~cells * 9 * capacity^2 tested pairs, dense
    vector work.
  * Sweeps of a row SUBSET (`rows_grid_counts`: the sharded engine's
    own rows against its halo; `rows_grid_neighbor_ids`: a query
    batch) walk the CSR form instead: for each of the 9 neighbor
    offsets every row gathers one `capacity`-wide segment window,
    chunked under a memory budget, so peak candidate memory is
    O(chunk * capacity) regardless of N and no work goes to cells no
    row needs. The padded (N, 9 * capacity) matrix (`candidate_table`)
    is kept for the Pallas kernels and as a parity oracle in tests.
  * `capacity` must bound the true max cell occupancy for exact
    results: members ranked past it are dropped from the slabs, the
    CSR window and the member table alike, and `build_grid` returns an
    `overflow` flag so callers can verify. A fixed-capacity member
    table `table[c, k]` (padded with -1) can be scattered from the
    sorted order (`build_grid(..., with_table=True)`; no sweep needs
    it). The auto capacity (`default_capacity`) is sized many Poisson
    standard deviations above the uniform-density mean, which covers
    RWP mobility comfortably.

Exactness: candidate cells are distinct (requires `ncell >= 3`, see
`make_grid_spec`) and the per-pair toroidal distance test is the same
expression the dense oracle uses, so counts are bit-identical to the
dense path — the parity contract tested in tests/test_neighbors.py.
When the world is too small to tessellate (`area / range < 3`)
`make_grid_spec` returns None and callers fall back to the dense sweep.

See DESIGN.md §Adaptations for the grid-vs-dense trade-off discussion.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

#: offsets of the 3x3 neighborhood, row-major
_NEIGH_OFFSETS = [(di, dj) for di in (-1, 0, 1) for dj in (-1, 0, 1)]

#: auto-chunking target: max candidate-matrix entries resident at once
_CHUNK_BUDGET = 1 << 22

#: rows per chunk of the sharded dense sweep (`rows_dense_counts`), which
#: pads its rows to a whole number of chunks
DENSE_CHUNK = 2048

#: resident bytes per (row, candidate-slot) entry of one chunked sweep:
#: the ~5 live (chunk, capacity) i32/f32 intermediates (indices, validity,
#: gathered positions, distances, mask) — what `chunk_entries` divides a
#: byte budget by to size the chunk
_BYTES_PER_CAND_ENTRY = 20


def chunk_entries(mem_budget_mb: int) -> int:
    """Candidate-entry budget for the chunked sweeps from a byte budget.

    0 (no budget set) keeps the historical `_CHUNK_BUDGET` default
    (~84 MB of transients); a positive budget divides by the resident
    bytes per entry, floored so a chunk always holds at least one row of
    any sane capacity."""
    if mem_budget_mb <= 0:
        return _CHUNK_BUDGET
    return max(1 << 12, (mem_budget_mb << 20) // _BYTES_PER_CAND_ENTRY)


def budget_capacity(ncell: int, mem_budget_mb: int) -> int:
    """Largest member-table capacity whose (ncell^2, capacity) i32 table
    fits in half the byte budget (the other half is the chunked sweep's
    transients). Callers clamp the density-derived capacity with this;
    a clamp below the true peak occupancy is *loud* (the `grid_overflow`
    flag / metric fires), never a silent undercount."""
    return max(1, (mem_budget_mb << 19) // (4 * ncell * ncell))


def toroidal_d2(a, b, area: float, axis: int = -1):
    """Squared toroidal distance between position arrays whose `axis`
    holds the (x, y) pair — the last by default, (..., 2).

    THE canonical per-pair expression: every backend (dense oracle,
    cell-list, Pallas kernels) must evaluate exactly this so the
    bit-identical parity contract is meaningful. The cell-slab sweep
    keeps its coordinates on the leading axis (`axis=0`), so the cell
    axis stays minor; the arithmetic is the same."""
    def sq(k):
        d = jnp.abs(jax.lax.index_in_dim(a, k, axis, keepdims=False)
                    - jax.lax.index_in_dim(b, k, axis, keepdims=False))
        d = jnp.minimum(d, area - d)
        return d ** 2

    return sq(0) + sq(1)


def dense_lp_counts(pos, lp, sender_mask, n_lp: int, area: float,
                    rng: float):
    """The dense O(N^2) oracle: counts[i, l] = #{j != i :
    toroidal_dist(i, j) <= rng, lp[j] == l}, zeroed for non-senders.
    Single source of truth — abm's dense backend and the kernel ref
    both delegate here."""
    n = pos.shape[0]
    in_range = toroidal_d2(pos[:, None, :], pos[None, :, :],
                           area) <= rng * rng
    in_range = in_range & ~jnp.eye(n, dtype=bool) & sender_mask[:, None]
    onehot = jax.nn.one_hot(lp, n_lp, dtype=jnp.float32)
    return (in_range.astype(jnp.float32) @ onehot).astype(jnp.int32)


def default_capacity(n: int, ncell: int) -> int:
    """Static per-cell capacity bound for n uniform SEs on ncell^2 cells.

    Mean occupancy plus 8 Poisson standard deviations plus slack: the
    probability any of ncell^2 cells exceeds this under uniform placement
    is negligible, and RWP mobility keeps the stationary distribution
    close to uniform (it mildly favors the center on a bounded square,
    but on the torus there is no boundary bias at all)."""
    mean = n / float(ncell * ncell)
    return int(math.ceil(mean + 8.0 * math.sqrt(mean) + 8.0))


def clustered_capacity(n: int, ncell: int, cell: float, n_clusters: int,
                       radius: float) -> int:
    """Static per-cell capacity bound for K-blob clustered placement.

    The uniform bound (`default_capacity`) assumes RWP's near-uniform
    stationary density; the hotspot/group/flock mobility models
    concentrate ~n/K SEs into blobs of the given radius, so the peak
    cell occupancy is the blob population times the fraction of the blob
    one cell covers. Factor 3 absorbs two blobs overlapping one cell
    plus center-peaking (blob density is not uniform either), and the
    uniform-background terms ride on top. Runs surface the
    `grid_overflow` metric, so an underestimate is loud, not silent."""
    per_blob = -(-n // max(n_clusters, 1))
    blob_area = math.pi * max(radius, cell / 2.0) ** 2
    peak = 3.0 * per_blob * min(1.0, cell * cell / blob_area)
    mean = n / float(ncell * ncell)
    return min(n, int(math.ceil(peak + mean + 8.0 * math.sqrt(max(mean, 1.0))
                                + 16.0)))


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static geometry of the cell grid (hashable: safe as a jit static)."""
    ncell: int  # cells per side
    cell: float  # cell side length, >= interaction_range
    capacity: int  # fixed member-table width (max SEs per cell)


def make_grid_spec(n: int, area: float, rng: float,
                   capacity: int = 0) -> Optional[GridSpec]:
    """Largest grid whose cell side still covers `rng`, or None.

    `ncell = floor(area / rng)` maximizes resolution subject to
    `cell >= rng` (the 3x3-coverage requirement). Below ncell=3 the 3x3
    sweep would alias cells through the torus wrap (the same cell would
    be visited more than once, double-counting pairs), so we return None
    and the caller uses the dense sweep — exact either way.
    """
    ncell = int(area // rng)
    if ncell < 3:
        return None
    cap = capacity if capacity > 0 else default_capacity(n, ncell)
    return GridSpec(ncell=ncell, cell=area / ncell, capacity=cap)


def cell_ids(pos, spec: GridSpec):
    """(N,) i32 cell id per position. THE binning expression — every
    consumer (grid build, sharded row queries) must use it so row cells
    and table cells always agree."""
    cxy = jnp.floor(pos / spec.cell).astype(jnp.int32)
    # pos < area, but pos/cell can round up to ncell at the seam
    cxy = jnp.clip(cxy, 0, spec.ncell - 1)
    return cxy[:, 0] * spec.ncell + cxy[:, 1]


def build_grid(pos, spec: GridSpec, valid=None, with_table=True):
    """Bin positions; returns dict with the sorted (CSR) layout and,
    optionally, the scattered member table.

    Keys: cell (N,) i32 cell id per SE; order (N,) the sort permutation;
    starts/counts (ncell^2,) segment offsets; table (ncell^2, capacity)
    member indices padded with -1 (only when `with_table`, which the
    O(N)-memory CSR sweep does not need — `rows_grid_counts` reads
    order/starts/counts directly); overflow () bool — True iff some cell
    holds more than `capacity` SEs (members beyond capacity are dropped
    from the table / the CSR segment window, so exactness requires
    overflow == False).

    `valid` (N,) bool optionally masks rows out of the structure
    entirely: invalid rows bin to the virtual cell ncell^2, so they
    occupy no member-table slot, count toward no cell, and can never
    trip `overflow`. The sharded engine uses this to build its local
    view grid over (own slots + received halo rows) where empty slots
    and halo padding are dead rows — `capacity` then only has to bound
    the density of *live* SEs. Invalid rows' `cell` entries hold the
    virtual id (callers must not index cell-shaped arrays with them).
    """
    n = pos.shape[0]
    ncells = spec.ncell * spec.ncell
    cell = cell_ids(pos, spec)
    if valid is not None:
        cell = jnp.where(valid, cell, ncells)
    order = jnp.argsort(cell)
    cell_sorted = cell[order]
    cids = jnp.arange(ncells, dtype=cell_sorted.dtype)
    starts = jnp.searchsorted(cell_sorted, cids)
    counts = jnp.searchsorted(cell_sorted, cids, side="right") - starts
    out = {
        "cell": cell,
        "order": order,
        "starts": starts,
        "counts": counts,
        "overflow": counts.max() > spec.capacity,
    }
    if with_table:
        # virtual-cell rows sort to the tail; their rank value is
        # irrelevant because the scatter below drops their out-of-bounds
        # cell id
        rank = jnp.arange(n) - starts[jnp.minimum(cell_sorted, ncells - 1)]
        table = jnp.full((ncells, spec.capacity), -1, jnp.int32)
        # ranks beyond capacity fall outside the table and are dropped
        out["table"] = table.at[cell_sorted, rank].set(
            order.astype(jnp.int32), mode="drop")
    return out


def neighbor_cells(cell, spec: GridSpec):
    """(N, 9) cell ids of the toroidal 3x3 neighborhood of each SE's cell."""
    cx, cy = cell // spec.ncell, cell % spec.ncell
    cols = [((cx + di) % spec.ncell) * spec.ncell + (cy + dj) % spec.ncell
            for di, dj in _NEIGH_OFFSETS]
    return jnp.stack(cols, axis=1)


def candidate_table(pos, spec: GridSpec, grid=None):
    """Per-SE candidate list: indices of every SE in the 3x3 neighborhood.

    Returns (cand, grid): cand (N, 9*capacity) i32, padded with -1 (the
    pad also covers the SE itself — self-exclusion is the caller's
    mask `cand != i`). This is the gather the pallas_grid kernel tiles.

    Overflowing `spec.capacity` would silently undercount (dropped
    members never become candidates), so it is reported loudly at
    runtime via jax.debug.print — it costs one comparison per call and
    fires only when the exactness contract is actually broken.
    """
    grid = grid if grid is not None else build_grid(pos, spec)
    jax.lax.cond(
        grid["overflow"],
        lambda mx: jax.debug.print(
            "WARNING repro.core.neighbors: max cell occupancy {mx} exceeds "
            "grid capacity %d — neighbor counts are UNDERCOUNTED; raise "
            "ABMConfig.grid_capacity or use the dense backend" % spec.capacity,
            mx=mx),
        lambda mx: None,
        grid["counts"].max())
    neigh = neighbor_cells(grid["cell"], spec)  # (N, 9)
    cand = grid["table"][neigh]  # (N, 9, capacity)
    return cand.reshape(cand.shape[0], -1), grid


def _counts_for_rows(pos, lp, n_lp: int, area: float, rng: float,
                     row_pos, row_idx, row_sender, row_cand):
    """Exact LP histogram for one chunk of senders given candidate lists.

    The histogram is n_lp masked vector reductions rather than a
    scatter-add: XLA lowers scatters serially on CPU, which would eat
    the entire cell-list win (n_lp is single-digit, the reductions
    vectorize)."""
    valid = (row_cand >= 0) & (row_cand != row_idx[:, None])
    j = jnp.clip(row_cand, 0, pos.shape[0] - 1)
    in_range = toroidal_d2(row_pos[:, None, :], pos[j], area) <= rng * rng
    mask = (in_range & valid & row_sender[:, None]).astype(jnp.int32)
    lpj = lp[j]
    cols = [jnp.sum(mask * (lpj == l), axis=1) for l in range(n_lp)]
    return jnp.stack(cols, axis=1)


def rows_counts_chunked(pos, lp, n_lp: int, area: float, rng: float,
                        row_pos, row_idx, row_sender, row_cand):
    """Exact LP histograms for an arbitrary *row set* of senders against
    the global (pos, lp) reference arrays, given per-row candidate lists.

    `row_idx` holds each row's index into the reference arrays (for
    self-exclusion). Rows are processed in chunks sized so the candidate
    matrix stays within a fixed budget, via `lax.map` — peak memory is
    O(chunk * width) rather than O(R * width). Over `candidate_table`
    it is the padded-table oracle the sweeps' parity tests compare
    against.
    """
    r = row_pos.shape[0]
    width = row_cand.shape[1]
    chunk = max(1, _CHUNK_BUDGET // max(width, 1))
    if r <= chunk:
        return _counts_for_rows(pos, lp, n_lp, area, rng, row_pos,
                                row_idx, row_sender, row_cand)
    n_chunks = -(-r // chunk)
    pad = n_chunks * chunk - r
    row_pos = jnp.pad(row_pos, ((0, pad), (0, 0)))
    row_idx = jnp.pad(row_idx, (0, pad), constant_values=-1)
    row_sender = jnp.pad(row_sender, (0, pad))  # padded rows: not senders
    row_cand = jnp.pad(row_cand, ((0, pad), (0, 0)), constant_values=-1)

    def one(args):
        rp, ri, rs, rc = args
        return _counts_for_rows(pos, lp, n_lp, area, rng, rp, ri, rs, rc)

    out = jax.lax.map(one, (row_pos.reshape(n_chunks, chunk, 2),
                            row_idx.reshape(n_chunks, chunk),
                            row_sender.reshape(n_chunks, chunk),
                            row_cand.reshape(n_chunks, chunk, width)))
    return out.reshape(n_chunks * chunk, n_lp)[:r]


def grid_walk(rows: int, capacity: int, budget_entries: int = 0) -> tuple:
    """The chunk rule of `rows_grid_counts` over `rows` rows: (chunk,
    n_chunks, slots). One chunk holds every row when the budget allows;
    otherwise rows are padded to `n_chunks` chunks of `chunk`. `slots`
    is the number of candidate slots the walk visits, padded rows
    included: every row tests 9 neighbour cells of `capacity` slots."""
    budget = budget_entries if budget_entries > 0 else _CHUNK_BUDGET
    chunk = max(1, budget // max(capacity, 1))
    if rows <= chunk:
        chunk, n_chunks = rows, 1
    else:
        n_chunks = -(-rows // chunk)
    return chunk, n_chunks, chunk * n_chunks * len(_NEIGH_OFFSETS) * capacity


def rows_grid_counts(pos, lp, n_lp: int, area: float, rng: float,
                     spec: GridSpec, grid, row_pos, row_idx, row_sender,
                     budget_entries: int = 0):
    """Cell-list counts for a row subset against a prebuilt global grid,
    via the CSR segment sweep — O(chunk * capacity) peak memory.

    For each of the 9 static neighbor offsets, every row gathers one
    `capacity`-wide window of the sorted order starting at its neighbor
    cell's segment offset (`order[starts[c] : starts[c] + capacity]`,
    masked by the segment count) and folds the in-range tests into the
    per-LP histogram immediately. Nothing the size of the old padded
    (R, 9 * capacity) candidate matrix is ever materialized: rows are
    processed in `lax.map` chunks sized so one offset's transients stay
    within `budget_entries` candidate entries (default `_CHUNK_BUDGET`;
    see `chunk_entries` for the byte-budget mapping).

    Segment windows are truncated at `capacity` exactly like the member
    table was (first `capacity` members in sorted order), so results are
    bit-identical to the dense oracle whenever `grid["overflow"]` is
    False and identically-undercounted (loud, never silent) when it is
    not. This is the query core of the per-shard halo path in
    parallel/lp_shard.py, which sweeps a row subset; sweeps of every row
    take the cell-slab sweep (`slab_lp_counts`)."""
    n = pos.shape[0]
    nc, cap = spec.ncell, spec.capacity
    order = grid["order"].astype(jnp.int32)
    starts = grid["starts"]
    # parity with the member table: members past `capacity` are dropped
    seg_cnt = jnp.minimum(grid["counts"], cap)
    row_cell = cell_ids(row_pos, spec)
    karange = jnp.arange(cap)

    def counts_for(rp, ri, rs, rc):
        cx, cy = rc // nc, rc % nc
        acc = jnp.zeros((rp.shape[0], n_lp), jnp.int32)
        for di, dj in _NEIGH_OFFSETS:
            ncid = ((cx + di) % nc) * nc + (cy + dj) % nc
            idx = starts[ncid][:, None] + karange[None, :]
            valid = karange[None, :] < seg_cnt[ncid][:, None]
            j = order[jnp.clip(idx, 0, n - 1)]
            valid = valid & (j != ri[:, None])
            in_range = toroidal_d2(rp[:, None, :], pos[j],
                                   area) <= rng * rng
            mask = (in_range & valid & rs[:, None]).astype(jnp.int32)
            lpj = lp[j]
            # n_lp masked reductions, not a scatter-add: XLA lowers
            # scatters serially on CPU (see _counts_for_rows)
            acc = acc + jnp.stack(
                [jnp.sum(mask * (lpj == l), axis=1) for l in range(n_lp)],
                axis=1)
        return acc

    r = row_pos.shape[0]
    chunk, n_chunks, _ = grid_walk(r, cap, budget_entries)
    if n_chunks == 1:
        return counts_for(row_pos, row_idx, row_sender, row_cell)
    pad = n_chunks * chunk - r
    rp = jnp.pad(row_pos, ((0, pad), (0, 0)))
    ri = jnp.pad(row_idx, (0, pad), constant_values=-1)
    rs = jnp.pad(row_sender, (0, pad))  # padded rows: not senders
    rc = jnp.pad(row_cell, (0, pad))
    out = jax.lax.map(lambda a: counts_for(*a),
                      (rp.reshape(n_chunks, chunk, 2),
                       ri.reshape(n_chunks, chunk),
                       rs.reshape(n_chunks, chunk),
                       rc.reshape(n_chunks, chunk)))
    return out.reshape(n_chunks * chunk, n_lp)[:r]


def rows_grid_neighbor_ids(pos, area: float, rng: float, spec: GridSpec,
                           grid, q_pos, q_row):
    """Indices (into `pos`) of every agent within `rng` of each query
    point, via the CSR cell list: (Q, 9 * capacity) i32, padded with -1.

    `q_row` is each query's own row index in `pos` (or -1), excluded
    from its result. Rows masked out of `grid` at build time (the
    open-world engine's dead slots) occupy no segment, so they can
    never appear. Segment windows truncate at `capacity` exactly like
    the counting sweep, so results are exact whenever
    `grid["overflow"]` is False. This is the query core of the service
    API's `query_neighbors` (repro.core.service) — Q is a request
    batch, not the population, so no chunking is needed."""
    n = pos.shape[0]
    nc, cap = spec.ncell, spec.capacity
    order = grid["order"].astype(jnp.int32)
    starts = grid["starts"]
    seg_cnt = jnp.minimum(grid["counts"], cap)
    rc = cell_ids(q_pos, spec)
    cx, cy = rc // nc, rc % nc
    karange = jnp.arange(cap)
    cols = []
    for di, dj in _NEIGH_OFFSETS:
        ncid = ((cx + di) % nc) * nc + (cy + dj) % nc
        idx = starts[ncid][:, None] + karange[None, :]
        ok = karange[None, :] < seg_cnt[ncid][:, None]
        j = order[jnp.clip(idx, 0, n - 1)]
        ok = ok & (j != q_row[:, None])
        ok = ok & (toroidal_d2(q_pos[:, None, :], pos[j], area) <= rng * rng)
        cols.append(jnp.where(ok, j, -1))
    return jnp.concatenate(cols, axis=1)


def slab_walk(ncell: int, capacity: int, budget_entries: int = 0) -> tuple:
    """The chunk rule of the cell-slab sweep (`slab_lp_counts`): (rows,
    n_chunks, slots). One offset of a chunk tests `rows` whole cell rows
    x (ncell + 2) positions (each row's two wrapped halo cells included)
    x capacity^2 slot pairs; the chunk holds every cell row when the
    budget allows, else as many as the budget affords (at least one),
    padded to `n_chunks` chunks of `rows`. `slots` is the number of slot
    pairs one receiver layer of the sweep tests, padding included: 9
    offsets per position."""
    budget = budget_entries if budget_entries > 0 else _CHUNK_BUDGET
    per_row = (ncell + 2) * capacity * capacity
    rows = max(1, budget // per_row)
    if rows >= ncell:
        rows, n_chunks = ncell, 1
    else:
        n_chunks = -(-ncell // rows)
    return rows, n_chunks, rows * n_chunks * len(_NEIGH_OFFSETS) * per_row


def _label_packing(capacity: int, n_lp: int) -> tuple:
    """(bits, per_word, n_words): one offset's count of a label is at
    most `capacity`, so `bits` hold it without carry; an int32 word
    packs `per_word` labels' counts, and `n_words` words hold all
    `n_lp`."""
    bits = capacity.bit_length()
    per_word = 31 // bits
    return bits, per_word, -(-n_lp // per_word)


def _slab_chunk(xy, weights, recv, n_lp: int, area: float, rng: float,
                stride: int, self_pairs: bool):
    """Per-slot LP histograms of one chunk of whole cell rows.

    The cell axis is the torus grid flattened with row `stride` =
    ncell + 2: each row carries a wrapped copy of the cell on either
    side. recv (2, cap, W) holds the receivers' x, y over the chunk's W
    = rows * stride positions; xy (2, cap, W + 2 * stride + 2) the
    candidates' x, y over the same positions widened by one halo row
    and one slot on each side, and weights (n_words, cap, same) their
    packed label weights (`_label_packing`: 1 << bits * label in the
    label's word, 0 in empty slots). Returns (cap, n_lp, W) i32: for
    each receiver slot, the in-range candidates of each label over the
    3x3 block of cells around it.

    Neighbour (di, dj) of position q is position q + di * stride + dj,
    so each offset's candidates are one static slice; nothing is
    gathered or rolled. Each offset sums the packed weights of its
    in-range candidates in one reduction over the candidate slots (a
    major axis), then unpacks. `self_pairs` drops the (a, a) diagonal
    of the centre cell: receivers and candidates are then the same
    slots."""
    cap, width = recv.shape[1], recv.shape[2]
    bits, per_word, _ = _label_packing(cap, n_lp)
    labels = jnp.arange(n_lp)
    shift = (bits * (labels % per_word))[None, :, None]
    acc = jnp.zeros((cap, n_lp, width), jnp.int32)
    for di, dj in _NEIGH_OFFSETS:
        at = 1 + (1 + di) * stride + dj
        c, w = (a[:, :, at:at + width] for a in (xy, weights))
        # (receiver slot a, candidate slot b, cell)
        hit = toroidal_d2(recv[:, :, None, :], c[:, None, :, :], area,
                          axis=0) <= rng * rng
        if self_pairs and di == 0 and dj == 0:
            hit = hit & ~jnp.eye(cap, dtype=bool)[:, :, None]
        packed = jnp.stack([jnp.sum(jnp.where(hit, w[k][None], 0), axis=1)
                            for k in range(w.shape[0])], axis=1)
        acc = acc + ((packed[:, labels // per_word] >> shift)
                     & ((1 << bits) - 1))
    return acc


def _cell_ranks(pos, spec: GridSpec, valid=None):
    """(cell, rank, max_rank): each SE's cell id (`cell_ids`; the
    virtual id ncell^2 for rows `valid` masks out) and its rank in the
    stable sort by cell — its position within the cell's CSR segment of
    `build_grid` — in id order, and the largest live rank (-1 with no
    live row). Two sorts and a running maximum: no gather or scatter."""
    n = pos.shape[0]
    ncells = spec.ncell * spec.ncell
    cell = cell_ids(pos, spec)
    if valid is not None:
        cell = jnp.where(valid, cell, ncells)
    iota = jnp.arange(n, dtype=jnp.int32)
    cell_sorted, order = jax.lax.sort((cell, iota), num_keys=1)
    first = cell_sorted != jnp.concatenate([cell_sorted[:1] - 1,
                                            cell_sorted[:-1]])
    rank_sorted = iota - jax.lax.cummax(jnp.where(first, iota, 0))
    max_rank = jnp.where(cell_sorted < ncells, rank_sorted, -1).max()
    rank = jax.lax.sort((order, rank_sorted), num_keys=1)[1]
    return cell, rank, max_rank


@functools.partial(jax.jit, static_argnames=("n_lp", "area", "rng", "spec",
                                             "budget_entries"))
def slab_lp_counts(pos, lp, sender_mask, n_lp: int, area: float, rng: float,
                   spec: GridSpec, valid=None, budget_entries: int = 0):
    """Cell-list LP histogram of every row, by a gather-free sweep of
    dense per-cell slabs. Returns (counts, overflow), counts (N, n_lp)
    i32 bit-identical to `dense_lp_counts` when overflow is False, and
    overflow the flag `build_grid` raises.

    Each live SE is ranked within its cell as `build_grid`'s sort ranks
    it (`_cell_ranks`); O(N) scatters lay x, y and packed label
    weights into slabs of shape (capacity, ncell, ncell), empty slots
    weightless (ranks >= capacity are dropped: the same members the CSR
    window and `candidate_table` drop). The slabs are wrapped by one
    cell on every side and flattened, cells minor (`_slab_chunk`), so
    each of the 9 neighbour offsets is a static shift; every (receiver
    slot, candidate slot) pair of the two cells is tested with
    `toroidal_d2`. One O(N) gather through (rank, cell) returns the
    histograms to id order.

    Receivers ranked past `capacity` (only under overflow) are swept in
    further layers of `capacity` ranks against the same candidate slabs,
    so every row gets the counts the CSR walk gives it. Without
    overflow the layer loop never runs. Cell rows are swept in
    `lax.map` chunks when one offset's capacity^2 slot pairs over all
    cells exceed `budget_entries` (`slab_walk`); every budget gives the
    same integers.

    `valid` masks rows out of the grid (the open-world engine's dead
    slots): they occupy no slot and get no counts, so the caller must
    not make them senders."""
    n = pos.shape[0]
    nc, cap = spec.ncell, spec.capacity
    stride = nc + 2
    cell, rank, max_rank = _cell_ranks(pos, spec, valid)
    live = cell < nc * nc
    cx, cy = cell // nc, cell % nc
    # each row's receiver position on the wrapped, flattened cell axis
    place = jnp.where(live, cx * stride + cy + 1, 0)
    rows, n_chunks, _ = slab_walk(nc, cap, budget_entries)
    pad_rows = n_chunks * rows - nc

    def slab(fields, k):
        """(len(fields), cap, (nc + 2 + pad_rows) * stride + 2): layer
        k's ranks [k * cap, (k + 1) * cap) wrapped by a cell on every
        side, then empty cell rows up to whole chunks, and one empty
        slot at either end; other ranks drop."""
        inside = live & (rank >= k * cap) & (rank < (k + 1) * cap)
        slot = jnp.where(inside, rank - k * cap, cap)
        # one scatter per field: a field axis in the scatter's window
        # makes XLA lay the slab out field-minor
        s = jnp.stack([jnp.zeros((cap, nc, nc), f.dtype).at[slot, cx, cy]
                       .set(f, mode="drop") for f in fields])
        s = jnp.pad(s, ((0, 0), (0, 0), (1, 1), (1, 1)), mode="wrap")
        s = jnp.pad(s, ((0, 0), (0, 0), (0, pad_rows), (0, 0)))
        s = jnp.pad(s.reshape(len(fields), cap, -1), ((0, 0), (0, 0), (1, 1)))
        return s, inside, slot

    bits, per_word, n_words = _label_packing(cap, n_lp)
    word = lp // per_word
    weight = jnp.left_shift(jnp.int32(1), bits * (lp - word * per_word))
    weights = [jnp.where((lp >= 0) & (word == k), weight, 0)
               for k in range(n_words)]
    xy = [pos[:, 0].astype(jnp.float32), pos[:, 1].astype(jnp.float32)]
    cand_xy, inside0, slot0 = slab(xy, 0)
    cand_w = slab(weights, 0)[0]
    width = rows * stride

    def sweep(recv, self_pairs):
        # receivers: the cell rows of the wrapped slab, halo columns
        # included (their counts are never read)
        recv = recv[:, :, 1 + stride:1 + stride + n_chunks * width]
        if n_chunks == 1:
            return _slab_chunk(cand_xy, cand_w, recv, n_lp, area, rng,
                               stride, self_pairs)

        def one(k):
            def window(a, start, size):
                return jax.lax.dynamic_slice_in_dim(a, start, size, axis=2)

            size = width + 2 * stride + 2
            return _slab_chunk(window(cand_xy, k * width, size),
                               window(cand_w, k * width, size),
                               window(recv, k * width, width), n_lp, area,
                               rng, stride, self_pairs)

        out = jax.lax.map(one, jnp.arange(n_chunks))
        return jnp.moveaxis(out, 0, 2).reshape(cap, n_lp, -1)

    def back(per_slot, inside, slot, out):
        got = per_slot[jnp.minimum(slot, cap - 1), :, place]
        return jnp.where(inside[:, None], got, out)

    out = back(sweep(cand_xy, True), inside0, slot0,
               jnp.zeros((n, n_lp), jnp.int32))

    def layer(carry):
        k, out = carry
        recv, inside, slot = slab(xy, k)
        return k + 1, back(sweep(recv, False), inside, slot, out)

    _, out = jax.lax.while_loop(lambda carry: carry[0] * cap <= max_rank,
                                layer, (jnp.int32(1), out))
    return jnp.where(sender_mask[:, None], out, 0), max_rank >= cap


def grid_lp_counts(pos, lp, sender_mask, n_lp: int, area: float, rng: float,
                   spec: GridSpec, budget_entries: int = 0):
    """Cell-list version of the dense LP histogram — bit-identical output.

    counts[i, l] = #{j != i : toroidal_dist(i, j) <= rng, lp[j] == l},
    zeroed for non-senders: the cell-slab sweep (`slab_lp_counts`)
    with every agent a row."""
    return slab_lp_counts(pos, lp, sender_mask, n_lp, area, rng, spec,
                          budget_entries=budget_entries)[0]


def halo_mask(cell_ref, row_cell, row_valid, spec: GridSpec):
    """Which reference agents lie in the halo of a row set?

    Returns a boolean mask over `cell_ref` (global per-agent cell ids):
    True for agents inside the 3x3 neighborhood of any cell occupied by
    a valid row. This is the *exact* halo set of the sharded engine —
    the agents a shard actually needs to resolve its own proximity
    queries, which the `halo_frac` metric counts (the sparse exchange
    transports a dilated superset of it; GAIA's clustering shrinks
    both, see parallel/lp_shard.py).
    """
    occ = jnp.zeros((spec.ncell * spec.ncell,), bool)
    safe_cell = jnp.where(row_valid, row_cell, spec.ncell * spec.ncell)
    occ = occ.at[safe_cell].set(True, mode="drop")
    occ2d = occ.reshape(spec.ncell, spec.ncell)
    halo2d = jnp.zeros_like(occ2d)
    for di, dj in _NEIGH_OFFSETS:
        halo2d = halo2d | jnp.roll(occ2d, (di, dj), axis=(0, 1))
    return halo2d.reshape(-1)[cell_ref]


def dilate_mask(occ, r: int):
    """Chebyshev (L-inf) dilation of a boolean cell mask by radius r on
    the torus: out[i, j] is True iff any cell within r rows AND r
    columns (wrapping) is True. r=1 is exactly the 3x3 neighborhood the
    proximity sweep visits; the sharded engine dilates by 1 + the
    per-step cell-displacement bound to turn "cells my SEs occupy now"
    into "cells whose occupants I may query next step" (the halo-need
    bitmap, see parallel/lp_shard.py).

    The L-inf ball is a square, so the dilation is separable: dilate
    rows, then columns. Works on any (..., ncell, ncell) batch; when
    2r+1 >= ncell a roll chain wraps all the way around and any occupied
    input correctly saturates the axis (need-everything)."""
    out = occ
    for axis in (-2, -1):
        acc = out
        for s in range(1, r + 1):
            acc = acc | jnp.roll(out, s, axis) | jnp.roll(out, -s, axis)
        out = acc
    return out


def cell_block_mean(pos, vec, spec: GridSpec, area: float, valid=None):
    """Per-SE mean of positions and of `vec` over the 3x3 cell block.

    The flocking-lite sensing kernel: returns (cdelta, vmean) where
    cdelta (N, 2) is the displacement from each SE to the centroid of
    the *other* SEs in its 3x3 neighborhood (zero when alone) and vmean
    (N, 2) is their mean `vec` (e.g. heading). O(N + ncell^2): one
    scatter-add binning pass plus nine rolled-grid accumulations — no
    member table, so grid capacity is irrelevant here.

    `valid` (open-world engine) drops dead rows from every aggregate
    (they bin to an out-of-bounds cell the scatter discards); their own
    output rows are garbage the caller must mask. With valid=None (or
    all True) results are unchanged.

    Torus correctness: position sums from cells rolled across the seam
    are shifted by ±area on the wrapped axis, so every block is summed
    in its center cell's locally-contiguous frame and `centroid - pos`
    is the true shortest displacement (needs ncell >= 3, which GridSpec
    guarantees). Determinism: the scatter-add consumes the same id-
    ordered arrays in the oracle and in the sharded engine's
    reconstructed state, so both reduce in the same order — the sharded
    bit-identity tests enforce this.
    """
    n, nc = pos.shape[0], spec.ncell
    cell = cell_ids(pos, spec)
    if valid is not None:
        cell = jnp.where(valid, cell, nc * nc)  # out of bounds -> dropped

    def bin2d(vals):
        return jnp.zeros((nc * nc,), jnp.float32).at[cell].add(
            vals, mode="drop").reshape(nc, nc)

    cnt = bin2d(jnp.ones((n,), jnp.float32))
    sx, sy = bin2d(pos[:, 0]), bin2d(pos[:, 1])
    vx, vy = bin2d(vec[:, 0]), bin2d(vec[:, 1])

    acc = [jnp.zeros((nc, nc), jnp.float32) for _ in range(5)]
    for di, dj in _NEIGH_OFFSETS:
        rc = jnp.roll(cnt, (di, dj), (0, 1))
        rsx = jnp.roll(sx, (di, dj), (0, 1))
        rsy = jnp.roll(sy, (di, dj), (0, 1))
        # unwrap the seam: cells rolled across it contribute coordinates
        # shifted by +-area on the rolled axis
        if di == 1:
            rsx = rsx.at[0, :].add(-area * rc[0, :])
        elif di == -1:
            rsx = rsx.at[-1, :].add(area * rc[-1, :])
        if dj == 1:
            rsy = rsy.at[:, 0].add(-area * rc[:, 0])
        elif dj == -1:
            rsy = rsy.at[:, -1].add(area * rc[:, -1])
        parts = (rc, rsx, rsy, jnp.roll(vx, (di, dj), (0, 1)),
                 jnp.roll(vy, (di, dj), (0, 1)))
        acc = [a + p for a, p in zip(acc, parts)]

    flat = [a.reshape(-1)[cell] for a in acc]
    others = jnp.maximum(flat[0] - 1.0, 1.0)  # exclude self; guard alone
    alone = (flat[0] - 1.0) <= 0.0
    csum = jnp.stack([flat[1], flat[2]], axis=1) - pos
    vsum = jnp.stack([flat[3], flat[4]], axis=1) - vec
    cdelta = jnp.where(alone[:, None], 0.0, csum / others[:, None] - pos)
    vmean = jnp.where(alone[:, None], 0.0, vsum / others[:, None])
    return cdelta, vmean


def rows_dense_counts(pos, lp, n_lp: int, area: float, rng: float,
                      row_pos, row_idx, row_sender,
                      chunk: int = DENSE_CHUNK):
    """Dense-sweep counts for a row subset against the global reference
    arrays — the sharded engine's fallback when the world is too small to
    tessellate. Reference entries with lp < 0 (empty shard slots) one-hot
    to zero and so never contribute, exactly like the grid path's
    candidate masking."""
    r = row_pos.shape[0]
    s = pos.shape[0]
    n_chunks = -(-r // chunk)
    pad = n_chunks * chunk - r
    row_pos = jnp.pad(row_pos, ((0, pad), (0, 0)))
    row_idx = jnp.pad(row_idx, (0, pad), constant_values=-1)
    row_sender = jnp.pad(row_sender, (0, pad))
    onehot = jax.nn.one_hot(lp, n_lp, dtype=jnp.float32)

    def one(args):
        rp, ri, rs = args
        in_range = toroidal_d2(rp[:, None, :], pos[None, :, :],
                               area) <= rng * rng
        not_self = ri[:, None] != jnp.arange(s)[None, :]
        mask = (in_range & not_self & rs[:, None]).astype(jnp.float32)
        return (mask @ onehot).astype(jnp.int32)

    out = jax.lax.map(one, (row_pos.reshape(n_chunks, chunk, 2),
                            row_idx.reshape(n_chunks, chunk),
                            row_sender.reshape(n_chunks, chunk)))
    return out.reshape(n_chunks * chunk, n_lp)[:r]


def dense_lp_counts_chunked(pos, lp, sender_mask, n_lp: int, area: float,
                            rng: float, chunk: int = 2048):
    """Row-chunked O(N^2) sweep: the dense oracle's math with O(chunk*N)
    peak memory instead of O(N^2), so it scales to N where materializing
    the full pair matrix would not fit. Used as the honest dense baseline
    in benchmarks/exp4_scaling.py (same flop count as the oracle)."""
    n = pos.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    row_pos = jnp.pad(pos, ((0, pad), (0, 0)))
    row_idx = jnp.arange(n + pad, dtype=jnp.int32)
    row_sender = jnp.pad(sender_mask, (0, pad))
    onehot = jax.nn.one_hot(lp, n_lp, dtype=jnp.float32)

    def one(args):
        rp, ri, rs = args
        in_range = toroidal_d2(rp[:, None, :], pos[None, :, :],
                               area) <= rng * rng
        not_self = ri[:, None] != jnp.arange(n)[None, :]
        mask = (in_range & not_self & rs[:, None]).astype(jnp.float32)
        return (mask @ onehot).astype(jnp.int32)

    out = jax.lax.map(one, (row_pos.reshape(n_chunks, chunk, 2),
                            row_idx.reshape(n_chunks, chunk),
                            row_sender.reshape(n_chunks, chunk)))
    return out.reshape(n_chunks * chunk, n_lp)[:n]
