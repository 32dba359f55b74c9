"""LP-per-device sharded execution of the GAIA engine (shard_map).

The single-device engine (`core/engine.py`) vectorizes every LP inside
one `lax.scan`, so "remote delivery" is purely an accounting fiction.
This module makes the distribution physical: LPs are mapped onto a 1-D
JAX device mesh (axis "lp"), and **each device owns the SE rows of its
LPs** — positions, waypoints, heuristic windows, migration state all
live in per-device slot buffers. Per step:

  * proximity/interaction counts are computed per-shard over a **sparse
    neighbor-only halo**: each device knows, one step in advance, which
    grid cells every device may query (the `halo_need` bitmaps, see
    below), packs exactly the boundary rows each peer needs into
    fixed-capacity per-pair buffers, and exchanges them with a single
    `all_to_all`. The PR-1 cell-list grid is then built over the local
    view (own rows + received halo) and each shard resolves only its
    own rows against its 3x3 candidate blocks. No position all-gather:
    what moves is the exchange set GAIA is shrinking, and the
    `bytes_on_wire` / `wire_flows` metrics count it exactly.
  * **halo-need double buffer** (the comm/compute overlap): the bitmap
    that steers step t+1's exchange is computed and psum-reduced at the
    tail of step t — per-device cell occupancy (plus the cells of rows
    pending migration toward each destination device) dilated by
    1 + ceil(max per-step displacement / cell). The dilation makes the
    one-step-stale footprint a sound superset of the true need (every
    in-range neighbor is guaranteed present in the receiver's view —
    tests/test_halo_exchange.py), and it removes the same-step global
    agreement round: the only same-step collective the proximity path
    needs is the one payload all_to_all, issued right after the (cheap)
    row-local mobility update so asynchronous-collective backends can
    overlap it with the independent own-row binning work.
  * LCR numerators/denominators, the candidate matrix, and all Eq. 5/6
    counters are accumulated across devices with `psum`.
  * GAIA migrations are **actual resharding ops**: when a migration's
    protocol delay elapses and the destination LP lives on another
    device, the SE's full state row (including its heuristic window) is
    packed into a fixed-capacity per-device migration buffer,
    all-gathered, and scattered into a free slot on the destination
    shard. The source slot is vacated (gid = lp = -1).

Bit-identity with the single-device oracle (the §4.2 transparency
invariant, extended to the execution layer): `sharding="lp_device"`
produces byte-identical states, series, and migration sequences to
`sharding="none"` on the same seed — see DESIGN.md §Neighbor-only halo
exchange for why each step phase preserves this exactly, and
tests/test_sharding.py + tests/test_halo_exchange.py for the enforced
contract. Three fixed capacities (slots per device, migration-buffer
rows, halo rows per device pair) must bound the true maxima for the
contract to hold; overflow is surfaced per step in the
`shard_overflow` metric (and asserted zero in the equivalence tests),
mirroring the cell-list grid's capacity discipline.

Wire accounting (`bytes_on_wire`, per-step; `wire_flows`, the per
(src dev, dst dev) byte matrix): JAX collectives still move fixed-size
buffers, so the numbers count the *useful* slots — packed halo rows at
12 B (pos + lp), admitted cross-device migration rows at their full
row size (state row + ring window), and, for the paths that still
reconstruct id-order state (flock mobility, the periodic repartition
hook), the valid rows of those gathers. Control-plane reductions (the
need bitmaps, free-slot counts, the psum'd counters) are excluded —
they are O(cells + LP^2), independent of the SE population. This is
exactly the traffic a ragged transport would put on the wire, so the
metric is the physical realization of what `halo_frac` only measured.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import balance as bal
from repro.core import heuristics as heu
from repro.core import neighbors
from repro.core import partition as part
from repro.core.abm import (epidemic_draws, epidemic_row_update,
                            epidemic_send_prob, init_abm,
                            max_step_displacement, mobility_row_apply,
                            mobility_row_draws, mobility_step,
                            row_local_mobility)
from repro.core.engine import (COMPILED_CACHE_SIZE, batch_summary,
                               window_summary)
from repro.obs import ledger as obs_ledger
from repro.obs import runtime as obs_runtime

#: per-SE state rows that migrate with an SE between shards ("mob" is
#: the per-SE mobility state: member offset / heading; "epi" the
#: workload infection flag — full-row packed)
_ROW_FIELDS = ("pos", "waypoint", "mob", "last_mig", "ptr", "since_eval",
               "epi", "gid")

#: bytes per halo row on the wire: pos (2 x f32) + lp (i32) — all a
#: receiver needs to resolve proximity + LP histograms against the row
HALO_ROW_BYTES = 12


def _halo_row_bytes(cfg) -> int:
    """Bytes per halo row for this config: the epidemic workload ships
    one extra i32 per row (the infectious-sender label the receiver's
    exposure sweep reads)."""
    return HALO_ROW_BYTES + (4 if cfg.abm.workload == "epidemic" else 0)


def _mig_row_bytes(window: int, n_lp: int, epidemic: bool = False) -> int:
    """Bytes per migrated SE row: the 8 _ROW_FIELDS (pos/waypoint/mob
    2 x f32 each, last_mig/ptr/since_eval/epi/gid i32) + dst i32 + the
    (window, n_lp) i32 heuristic ring rows that travel with the SE. The
    `epi` flag only counts for epidemic runs — it is carried (zero)
    either way, but a ragged transport would elide a constant column."""
    return 44 + (4 if epidemic else 0) + 4 * window * n_lp


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Static geometry of the LP-per-device layout."""
    n_dev: int  # devices on the "lp" mesh axis
    n_lp: int
    n_se: int
    cap: int  # SE slots per device (must bound max per-device population)
    mig_cap: int  # migration-buffer rows per device per step
    halo_cap: int  # halo rows per (src, dst) device pair per step
    grid: Optional[neighbors.GridSpec]  # local-view cell list (live SEs)

    @property
    def n_slots(self) -> int:
        return self.n_dev * self.cap


def dev_of_lp(lp, spec: ShardSpec):
    """Block LP->device map: device d owns a contiguous LP range."""
    return (lp * spec.n_dev) // spec.n_lp


def _sparse_halo(spec: ShardSpec) -> bool:
    """Does this layout run the neighbor-only exchange? Needs a grid
    (footprints are cell bitmaps) and a second device to talk to."""
    return spec.grid is not None and spec.n_dev > 1


def _dilation_radius(spec: ShardSpec, abm) -> int:
    """Cells of Chebyshev dilation that turn step-t occupancy into a
    sound step-t+1 need set: 1 for the 3x3 proximity block + the cell
    shift bound of one mobility step (a move of at most `disp` per axis
    crosses at most floor(disp/cell) + 1 cell boundaries)."""
    return 2 + int(max_step_displacement(abm) // spec.grid.cell)


def make_shard_spec(cfg) -> ShardSpec:
    """Resolve the sharded layout for an EngineConfig (sharding="lp_device")."""
    abm = cfg.abm
    n, L = abm.n_se, abm.n_lp
    avail = len(jax.devices())
    d = cfg.n_devices if cfg.n_devices > 0 else avail
    if d > avail:
        raise ValueError(
            f"n_devices={d} but only {avail} JAX devices are visible. On "
            "the CPU, XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "forces N host devices (set it before jax is imported); on an "
            "accelerator the host has only the chips it has — lower "
            "n_devices (0 = all visible)")
    d = min(d, L)  # never more devices than LPs
    backend = abm.resolved_backend()
    if backend.startswith("pallas"):
        raise NotImplementedError(
            f"sharding='lp_device' supports proximity_backend 'grid' and "
            f"'dense', not {backend!r} (the Pallas kernels are per-device "
            "TPU kernels; run them under sharding='none')")
    budget_mb = cfg.abm.mem_budget_mb  # engine knob propagates into abm
    if cfg.shard_capacity > 0:
        cap = cfg.shard_capacity
    elif d == 1:
        cap = n
    else:
        # 2x the balanced share: covers symmetric balance exactly and
        # asymmetric drift up to a 2/d capacity share; override via
        # EngineConfig.shard_capacity for more skewed profiles.
        cap = min(n, -(-2 * n // d) + 8)
    # a device can never have more than `cap` same-step leavers, so an
    # explicit mig_capacity above that is clamped (not an error)
    if cfg.mig_capacity > 0:
        mig_cap = min(cap, cfg.mig_capacity)
    else:
        mig_cap = min(cap, max(32, cap // 2))
        if budget_mb > 0 and d > 1:
            # budgeted auto: the all-gathered migration buffer is
            # (d * mig_cap) rows of _mig_row_bytes each per device —
            # give it a quarter of the budget. Exact-or-loud: a
            # same-step leaver burst beyond the buffer defers rows and
            # raises shard_overflow, never drops SEs.
            w = cfg.heuristic.kappa if cfg.heuristic.kind == 1 \
                else cfg.heuristic.omega
            rows = (budget_mb << 18) // (d * _mig_row_bytes(
                w, L, abm.workload == "epidemic"))
            mig_cap = min(mig_cap, max(16, rows))
    grid = None
    if backend == "grid":
        # the mobility-aware oracle geometry: the local view (own rows +
        # received halo) only ever tables *live* SEs (build_grid masks
        # dead slots/padding), so the per-cell bound for the n true SEs
        # applies as-is — no pad allowance, roughly halving the 3x3
        # candidate width vs. tabling all n_dev*cap slots
        grid = abm.grid_spec()
    if grid is None or d == 1:
        halo_cap = 1  # no exchange: dense fallback / single device
    elif cfg.halo_capacity > 0:
        halo_cap = min(cfg.halo_capacity, cap)
    elif budget_mb > 0:
        # budgeted auto instead of the worst case: send + recv buffers
        # are 2 * d * halo_cap rows of HALO_ROW_BYTES per device — give
        # them a quarter of the budget. Safe-by-alarm, not by bound: a
        # peer needing more rows than this from one device trips
        # shard_overflow (exact-or-loud), and GAIA's clustering is what
        # keeps real needs far below the worst case.
        rows = (budget_mb << 18) // (2 * d * _halo_row_bytes(cfg))
        halo_cap = min(cap, max(32, rows))
    else:
        # a peer can need every row a device owns (e.g. the random
        # initial partition scatters each LP across the whole torus), so
        # only cap itself is safe for arbitrary partitions; tighten via
        # EngineConfig.halo_capacity (or a mem_budget_mb) once GAIA has
        # clustered the shards
        halo_cap = cap
    return ShardSpec(n_dev=d, n_lp=L, n_se=n, cap=cap, mig_cap=mig_cap,
                     halo_cap=halo_cap, grid=grid)


def make_mesh(spec: ShardSpec) -> Mesh:
    return Mesh(np.array(jax.devices()[:spec.n_dev]), ("lp",))


# ---------------------------------------------------------------------------
# halo-need bitmaps
# ---------------------------------------------------------------------------


def halo_need_bitmaps(pos, valid, pending_dst, spec: ShardSpec, abm):
    """(n_dev, ncell^2) bool: cells whose occupants device d may query
    *next* step — its dilated spatial footprint.

    Device d's footprint is the set of cells occupied by its valid
    slots, plus the cells of every row currently pending migration
    toward one of d's LPs (the row lands on d when its delay elapses,
    and d's own bitmap cannot know about it in advance), Chebyshev-
    dilated by `_dilation_radius` (3x3 proximity + one step of motion).
    A superset is always sound — rows sent but not queried cost wire
    bytes, never correctness.

    This global slot-major version seeds `init_sharded` and serves as
    the reference the property tests check against; `_shard_step`
    computes the identical bitmaps distributedly (each device
    contributes its rows, psum ORs them) at the tail of every step.
    """
    g = spec.grid
    ncells = g.ncell * g.ncell
    dev = jnp.arange(pos.shape[0], dtype=jnp.int32) // spec.cap
    cell = neighbors.cell_ids(pos, g)
    safe_cell = jnp.where(valid, cell, ncells)  # invalid -> dropped
    contrib = jnp.zeros((spec.n_dev, ncells), bool)
    contrib = contrib.at[dev, safe_cell].set(True, mode="drop")
    pend = valid & (pending_dst >= 0)
    pdev = dev_of_lp(jnp.maximum(pending_dst, 0), spec)
    contrib = contrib.at[jnp.where(pend, pdev, spec.n_dev),
                         safe_cell].set(True, mode="drop")
    return neighbors.dilate_mask(
        contrib.reshape(spec.n_dev, g.ncell, g.ncell),
        _dilation_radius(spec, abm)).reshape(spec.n_dev, ncells)


# ---------------------------------------------------------------------------
# init / unshard
# ---------------------------------------------------------------------------


def init_sharded(key, cfg, spec: ShardSpec):
    """Slot-major engine state: device d owns slots [d*cap, (d+1)*cap).

    Consumes the PRNG exactly like `engine.init_engine` (same k1/k2
    split), so SE i's initial position/waypoint/LP are bit-identical to
    the oracle's row i. Empty slots get spread-out pad positions from an
    independent stream (they must not pile into one grid cell) and
    lp = gid = -1. Under the sparse halo the state also carries the
    initial `halo_need` bitmaps (the double buffer's first entry),
    computed from the initial placement by `halo_need_bitmaps`.
    """
    n, L, S = spec.n_se, spec.n_lp, spec.n_slots
    k1, k2 = jax.random.split(key)
    st = init_abm(k1, cfg.abm)
    hst = heu.init_state(cfg.heuristic, n, L)

    lp = np.asarray(st["lp"])
    dev = np.asarray(dev_of_lp(jnp.asarray(lp), spec))
    counts = np.bincount(dev, minlength=spec.n_dev)
    if counts.max() > spec.cap:
        raise ValueError(
            f"initial per-device population {counts.max()} exceeds "
            f"shard_capacity {spec.cap}; raise EngineConfig.shard_capacity")
    order = np.argsort(dev, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(n) - starts[dev[order]]
    slot_of_se = np.empty(n, np.int64)
    slot_of_se[order] = dev[order] * spec.cap + rank

    k_pad = jax.random.fold_in(key, 0x5107)
    pad_pos = jax.random.uniform(k_pad, (S, 2), maxval=cfg.abm.area)

    def scat(x, fill):
        out = jnp.full((S,) + x.shape[1:], fill, x.dtype)
        return out.at[slot_of_se].set(x)

    ring = jnp.zeros((hst["ring"].shape[0], S, L), hst["ring"].dtype)
    ring = ring.at[:, slot_of_se, :].set(hst["ring"])
    state = {
        "pos": pad_pos.at[slot_of_se].set(st["pos"]),
        "waypoint": pad_pos.at[slot_of_se].set(st["waypoint"]),
        "mob": jnp.zeros((S, 2), jnp.float32).at[slot_of_se].set(st["mob"]),
        "mob_g": st["mob_g"],  # global mobility rows: replicated
        "lp": scat(st["lp"], -1),
        "epi": scat(st["epi"], 0),
        "gid": scat(jnp.arange(n, dtype=jnp.int32), -1),
        "pending_dst": jnp.full((S,), -1, jnp.int32),
        "pending_eta": jnp.full((S,), -1, jnp.int32),
        "ring": ring,
        "ptr": scat(hst["ptr"], 0),
        "since_eval": scat(hst["since_eval"], 0),
        "last_mig": scat(hst["last_mig"], -10**6),
        "key": k2,
        "t": jnp.int32(0),
    }
    live = cfg.initial_live()
    if cfg.open_world and live < n:
        # open world: ids [live, n) start as free slots (gid = lp = -1),
        # mirroring the oracle's lp < 0 dead mask. Every SE was scattered
        # first so the initial placement (and the live prefix's bits)
        # matches the oracle's row-for-row.
        dead = state["gid"] >= live
        state["gid"] = jnp.where(dead, -1, state["gid"])
        state["lp"] = jnp.where(dead, -1, state["lp"])
    if _sparse_halo(spec):
        state["halo_need"] = halo_need_bitmaps(
            state["pos"], state["gid"] >= 0, state["pending_dst"], spec,
            cfg.abm)
    return state


def unshard_state(state, spec: ShardSpec):
    """Scatter slot-major state back to gid-order — the oracle's layout,
    so sharded and single-device final states compare byte-for-byte.
    The `halo_need` double buffer is execution-layer plumbing with no
    oracle counterpart, so it is dropped here."""
    n = spec.n_se
    gid = state["gid"]
    tgt = jnp.where(gid >= 0, gid, n)  # -1 -> out of bounds -> dropped

    def scat(x):
        out = jnp.zeros((n,) + x.shape[1:], x.dtype)
        return out.at[tgt].set(x, mode="drop")

    ring = jnp.zeros((state["ring"].shape[0], n, spec.n_lp),
                     state["ring"].dtype)
    ring = ring.at[:, tgt, :].set(state["ring"], mode="drop")
    return {
        "pos": scat(state["pos"]),
        "waypoint": scat(state["waypoint"]),
        "mob": scat(state["mob"]),
        "mob_g": state["mob_g"],
        "lp": scat(state["lp"]),
        "epi": scat(state["epi"]),
        "pending_dst": scat(state["pending_dst"]),
        "pending_eta": scat(state["pending_eta"]),
        "ring": ring,
        "ptr": scat(state["ptr"]),
        "since_eval": scat(state["since_eval"]),
        "last_mig": scat(state["last_mig"]),
        "key": state["key"],
        "t": state["t"],
    }


# ---------------------------------------------------------------------------
# one sharded timestep
# ---------------------------------------------------------------------------


def _apply_arrivals(f, t, cfg, spec: ShardSpec, me):
    """Complete in-flight migrations: local ones flip `lp` in place;
    cross-device ones are packed, all-gathered, and scattered into free
    destination slots (the resharding op). Returns (fields, overflow,
    mig_wire) where mig_wire is the (n_dev, n_dev) byte matrix of the
    admitted cross-device rows — the state transfer a ragged transport
    would put on the wire this step (replicated: every device computes
    the same admission decision, so the same matrix).

    Overflow never destroys SEs: a leaver that does not fit the
    migration buffer, or whose destination has no free slot this step,
    keeps its slot and its pending state and retries next step (the
    arrival test is `eta <= t`). Every device computes the same
    admission decision from the gathered buffer + gathered free counts,
    so source vacates exactly the rows the destination inserts. The
    deferral still diverges from the single-device oracle (which has no
    capacity limits), so `shard_overflow` stays an equivalence alarm —
    but the simulation remains population-preserving and valid."""
    B = spec.mig_cap
    due = ((f["pending_eta"] >= 0) & (f["pending_eta"] <= t)
           & (f["gid"] >= 0))
    dst = f["pending_dst"]
    dst_dev = dev_of_lp(jnp.maximum(dst, 0), spec)
    stay = due & (dst_dev == me)
    leave = due & (dst_dev != me)

    f = dict(f)
    f["lp"] = jnp.where(stay, dst, f["lp"])
    f["pending_dst"] = jnp.where(stay, -1, f["pending_dst"])
    f["pending_eta"] = jnp.where(stay, -1, f["pending_eta"])

    # pack leavers into the fixed migration buffer, gather-style (a
    # scatter over all cap slots would serialize on CPU): stable argsort
    # puts leaver slot ids first in ascending slot order
    leaver_slots = jnp.argsort(~leave, stable=True)[:B]
    n_leave = leave.sum()
    is_row = jnp.arange(B) < n_leave
    mig_overflow = n_leave > B

    def pack(x, fill):
        v = x[leaver_slots]
        shape = (B,) + (1,) * (v.ndim - 1)
        return jnp.where(is_row.reshape(shape), v, fill)

    buf = {k: pack(f[k], 0) for k in _ROW_FIELDS if k != "gid"}
    buf["gid"] = pack(f["gid"], -1)
    buf["dst"] = pack(dst, -1)
    # gather the leavers' ring rows on the slot axis (no full transpose)
    buf["ring"] = jnp.where(is_row[:, None, None],
                            jnp.moveaxis(f["ring"][:, leaver_slots, :], 1, 0),
                            0)  # (B, w, L)

    # exchange; admission is decided identically on every device
    g = {k: jax.lax.all_gather(v, "lp", axis=0, tiled=True)
         for k, v in buf.items()}  # (n_dev*B, ...)
    free = f["gid"] < 0
    free_counts = jax.lax.all_gather(free.sum(), "lp")  # (n_dev,)
    g_dev = dev_of_lp(jnp.maximum(g["dst"], 0), spec)
    g_valid = g["gid"] >= 0
    # rank of each buffer row among rows bound for the same destination
    per_dev = (g_valid[None, :]
               & (g_dev[None, :] == jnp.arange(spec.n_dev)[:, None]))
    rank = (jnp.cumsum(per_dev, axis=1) - 1)[g_dev, jnp.arange(g_dev.shape[0])]
    admitted = g_valid & (rank < free_counts[g_dev])
    cap_overflow = (g_valid & ~admitted).any()

    # the admitted cross-device rows are the priced migration payload
    src_dev = jnp.arange(spec.n_dev * B, dtype=jnp.int32) // B
    crossed = admitted & (g_dev != src_dev)
    mig_wire = jnp.zeros((spec.n_dev, spec.n_dev), jnp.int32).at[
        src_dev, g_dev].add(crossed.astype(jnp.int32)
                            * _mig_row_bytes(
                                f["ring"].shape[0], spec.n_lp,
                                cfg.abm.workload == "epidemic"))

    # vacate exactly the admitted leavers (deferred rows keep slot +
    # pending state); their ring rows go stale rather than zeroed —
    # stale rows are inert: evaluate() masks by valid, and arrivals
    # overwrite the whole row
    adm_local = admitted[me * B + jnp.arange(B)]
    vacate = jnp.zeros_like(leave).at[leaver_slots].set(
        is_row & adm_local, mode="drop")
    f["gid"] = jnp.where(vacate, -1, f["gid"])
    f["lp"] = jnp.where(vacate, -1, f["lp"])
    f["pending_dst"] = jnp.where(vacate, -1, f["pending_dst"])
    f["pending_eta"] = jnp.where(vacate, -1, f["pending_eta"])
    f["last_mig"] = jnp.where(vacate, -10**6, f["last_mig"])
    f["ptr"] = jnp.where(vacate, 0, f["ptr"])
    f["since_eval"] = jnp.where(vacate, 0, f["since_eval"])

    # insert admitted rows bound for this device into its free slots.
    # NOTE: free slots were counted before vacating, so a slot freed by
    # this step's departures is never handed to this step's arrivals —
    # both sides of the admission decision see the same free count.
    mine = admitted & (g_dev == me)
    free_order = jnp.argsort(~free, stable=True)  # free slots first, asc
    arr_rank = jnp.cumsum(mine) - 1
    target = jnp.where(
        mine, free_order[jnp.clip(arr_rank, 0, spec.cap - 1)], spec.cap)

    for k in _ROW_FIELDS:
        f[k] = f[k].at[target].set(g[k], mode="drop")
    f["lp"] = f["lp"].at[target].set(g["dst"], mode="drop")
    f["pending_dst"] = f["pending_dst"].at[target].set(-1, mode="drop")
    f["pending_eta"] = f["pending_eta"].at[target].set(-1, mode="drop")
    f["ring"] = f["ring"].at[:, target, :].set(
        jnp.moveaxis(g["ring"], 0, 1), mode="drop")
    overflow = mig_overflow | cap_overflow
    return f, overflow, mig_wire


def _gather_row_bytes(cfg) -> int:
    """Static per-valid-row byte price of the id-order reconstruction
    gathers a step performs (flock mobility and/or the periodic
    repartition hook) — the exact accumulation the fused step used to
    compute inline, now shared by the fused and traced paths."""
    row_local = row_local_mobility(cfg.abm)
    grb = 0 if row_local else 20  # flock: pos + mob + gid
    if cfg.repartition_every > 0:
        # post-mobility pos + gid per valid row; gid rides the flock
        # gather when one already happened, leaving pos only
        grb += 12 if row_local else 8
        if part.uses_prev(part.from_engine(cfg)):
            grb += 4  # hysteresis backends read the id-order map too
    return grb


def _sharded_phases(cfg, spec: ShardSpec):
    """Ordered (name, fn) phase decomposition of the per-device step
    body. Each fn maps a phase-context dict `px` (per-SE fields under
    "f", plus intermediates earlier phases added) to the grown dict.
    `_shard_step` composes the phases fused, each under its
    `step.<phase>` named scope, so the compiled scan is the historical
    program."""
    abm = cfg.abm
    n, L, C = spec.n_se, spec.n_lp, spec.cap
    D = spec.n_dev

    def ph_migrate(px):
        # 1. complete in-flight migrations (the resharding op)
        me = jax.lax.axis_index("lp")
        f, reshard_overflow, wire = _apply_arrivals(
            dict(px["f"]), px["t"], cfg, spec, me)
        valid = f["gid"] >= 0
        safe_gid = jnp.clip(f["gid"], 0, n - 1)
        n_valid = valid.sum()
        all_valid = jax.lax.psum(n_valid, "lp")
        return dict(px, f=f, wire=wire, reshard_overflow=reshard_overflow,
                    valid=valid, safe_gid=safe_gid, n_valid=n_valid,
                    all_valid=all_valid)

    def ph_mobility(px):
        # 2. model evolution. The row-local models (rwp/hotspot/group)
        # factor into full-array id-order draws + an elementwise apply:
        # each device computes the same draw arrays, gathers its rows by
        # SE id, and moves them in place — every SE sees the same
        # randomness wherever it is hosted (bit-identity), and no
        # position leaves the device. Flock reads global cell aggregates
        # (a float scatter-add whose reduction order must match the
        # oracle), so each device reconstructs the id-order arrays from
        # an all-gather, advances them with the *same* `mobility_step`
        # the oracle runs, and takes its own rows back — bit-identity by
        # construction (see DESIGN.md).
        f = dict(px["f"])
        valid, safe_gid = px["valid"], px["safe_gid"]
        k_move = jax.random.wrap_key_data(px["k_move"])
        k_send = jax.random.wrap_key_data(px["k_send"])
        out = dict(px)
        if row_local_mobility(abm):
            draws, mob_g = mobility_row_draws(k_move, n, f["mob_g"], abm)
            my_draws = {k: v[safe_gid] for k, v in draws.items()}
            new_pos, new_wp = mobility_row_apply(f["pos"], f["waypoint"],
                                                 f["mob"], my_draws, abm)
            f["pos"] = jnp.where(valid[:, None], new_pos, f["pos"])
            f["waypoint"] = jnp.where(valid[:, None], new_wp, f["waypoint"])
            f["mob_g"] = mob_g
        else:
            pos_all = jax.lax.all_gather(f["pos"], "lp", axis=0, tiled=True)
            mob_all = jax.lax.all_gather(f["mob"], "lp", axis=0, tiled=True)
            gid_all = jax.lax.all_gather(f["gid"], "lp", axis=0, tiled=True)
            tgt = jnp.where(gid_all >= 0, gid_all, n)  # pads -> dropped
            pos_n = jnp.zeros((n, 2), f["pos"].dtype).at[tgt].set(
                pos_all, mode="drop")
            mob_n = jnp.zeros((n, 2), f["mob"].dtype).at[tgt].set(
                mob_all, mode="drop")
            wp_n = jnp.zeros((n, 2), jnp.float32)  # unused by flock
            # open world: the flock aggregates must exclude dead ids
            # exactly like the oracle's valid mask (live rows scatter
            # True; dead ids stay False — only live rows ride the gather)
            valid_n = jnp.zeros((n,), bool).at[tgt].set(
                True, mode="drop") if cfg.open_world else None
            pos_n, _, mob_n, mob_g = mobility_step(k_move, pos_n, wp_n,
                                                   mob_n, f["mob_g"], abm,
                                                   valid=valid_n)
            f["pos"] = jnp.where(valid[:, None], pos_n[safe_gid], f["pos"])
            f["mob"] = jnp.where(valid[:, None], mob_n[safe_gid], f["mob"])
            f["mob_g"] = mob_g
            out["gid_all"] = gid_all  # shared by the repartition hook
        if abm.workload == "epidemic":
            # mirror of engine.ph_mobility's boosted sender draw: the
            # full-size id-order uniforms are gathered by SE id, the
            # per-row threshold reads the slot's own infection flag —
            # same randomness, same comparison, wherever the row lives
            u = jax.random.uniform(k_send, (n,))[safe_gid]
            sender = valid & (u < epidemic_send_prob(f["epi"], abm))
        else:
            sender = valid & jax.random.bernoulli(
                k_send, abm.p_interact, (n,))[safe_gid]
        out.update(f=f, sender=sender)
        return out

    def ph_halo(px):
        # 3. halo exchange: assemble the local proximity view. Epidemic
        # runs ship one extra label per row — 1 on infectious rows that
        # sent this step, 0 on other live rows, -1 on padding — so the
        # receiver's exposure sweep (ph_workload) reads the same labels
        # the oracle builds in id order.
        me = jax.lax.axis_index("lp")
        f, valid, wire = px["f"], px["valid"], px["wire"]
        epidemic = abm.workload == "epidemic"
        if epidemic:
            own_labels = jnp.where(
                valid, ((f["epi"] > 0) & px["sender"]).astype(jnp.int32),
                -1)
        halo_overflow = jnp.bool_(False)
        halo_n = jnp.int32(0)
        if spec.grid is not None:
            gspec = spec.grid
            nc = gspec.ncell
            ncells = nc * nc
            cellC = neighbors.cell_ids(f["pos"], gspec)
            if D > 1:
                hc = spec.halo_cap
                # pack, per peer, exactly the rows its (one-step-stale,
                # dilation-covered) need bitmap asks for
                need = f["halo_need"]  # (D, ncells), negotiated at t-1
                want = need[:, jnp.where(valid, cellC, 0)]  # (D, C)
                send = want & valid[None, :] & \
                    (jnp.arange(D, dtype=jnp.int32) != me)[:, None]
                cnt = send.sum(axis=1)
                order = jnp.argsort(~send, axis=1, stable=True)[:, :hc]
                is_row = jnp.arange(hc)[None, :] < cnt[:, None]
                send_pos = jnp.where(is_row[..., None], f["pos"][order], 0.0)
                send_lp = jnp.where(is_row, f["lp"][order], -1)
                halo_overflow = (cnt > hc).any()
                # the one same-step collective of the proximity path
                recv_pos = jax.lax.all_to_all(send_pos, "lp", split_axis=0,
                                              concat_axis=0, tiled=True)
                recv_lp = jax.lax.all_to_all(send_lp, "lp", split_axis=0,
                                             concat_axis=0, tiled=True)
                view_pos = jnp.concatenate([f["pos"],
                                            recv_pos.reshape(D * hc, 2)])
                view_lp = jnp.concatenate([f["lp"], recv_lp.reshape(D * hc)])
                if epidemic:
                    send_eis = jnp.where(is_row, own_labels[order], -1)
                    recv_eis = jax.lax.all_to_all(
                        send_eis, "lp", split_axis=0, concat_axis=0,
                        tiled=True)
                    view_eis = jnp.concatenate(
                        [own_labels, recv_eis.reshape(D * hc)])
                packed = jnp.minimum(cnt, hc)
                wire = wire + jax.lax.psum(
                    jnp.zeros((D, D), jnp.int32).at[me].set(
                        packed * _halo_row_bytes(cfg)), "lp")
                # exact halo (the pre-existing halo_frac semantics):
                # received rows inside this shard's true 3x3 need *now*.
                # Exchange soundness guarantees every such row was
                # received, so the sparse path measures the same quantity
                # the full-gather transport did — trajectories stay
                # baseline-comparable.
                occ = jnp.zeros((ncells,), bool).at[
                    jnp.where(valid, cellC, ncells)].set(True, mode="drop")
                exact = neighbors.dilate_mask(occ.reshape(nc, nc),
                                              1).reshape(-1)
                cellR = neighbors.cell_ids(recv_pos.reshape(D * hc, 2),
                                           gspec)
                halo_n = ((recv_lp.reshape(-1) >= 0) & exact[cellR]).sum()
            else:
                view_pos, view_lp = f["pos"], f["lp"]
                if epidemic:
                    view_eis = own_labels
            out = dict(px, wire=wire, cellC=cellC, view_pos=view_pos,
                       view_lp=view_lp, halo_overflow=halo_overflow,
                       halo_n=halo_n)
            if epidemic:
                out["view_eis"] = view_eis
            return out
        # dense fallback (world too small to tessellate): the original
        # full-gather transport — every position/LP to every device
        pos_g = jax.lax.all_gather(f["pos"], "lp", axis=0, tiled=True)
        lp_g = jax.lax.all_gather(f["lp"], "lp", axis=0, tiled=True)
        halo_n = px["all_valid"] - px["n_valid"]  # every remote needed
        if D > 1:
            vcnt = jax.lax.all_gather(px["n_valid"], "lp")  # (D,)
            wire = wire + (vcnt[:, None] * _halo_row_bytes(cfg)
                           * (1 - jnp.eye(D, dtype=jnp.int32)))
        out = dict(px, wire=wire, pos_g=pos_g, lp_g=lp_g,
                   halo_overflow=halo_overflow, halo_n=halo_n)
        if epidemic:
            out["eis_g"] = jax.lax.all_gather(own_labels, "lp", axis=0,
                                              tiled=True)
        return out

    def ph_proximity(px):
        # 3a. per-shard proximity counts over the assembled view
        f, valid, sender = px["f"], px["valid"], px["sender"]
        if spec.grid is not None:
            gspec = spec.grid
            ncells = gspec.ncell * gspec.ncell
            grid = neighbors.build_grid(px["view_pos"], gspec,
                                        valid=px["view_lp"] >= 0,
                                        with_table=False)
            # visit local rows in cell-sorted order (same trick as the
            # engine path: the CSR segment gathers get spatial locality);
            # integer counts scatter back to slot order exactly
            row_order = jnp.argsort(jnp.where(valid, px["cellC"], ncells),
                                    stable=True).astype(jnp.int32)
            out = neighbors.rows_grid_counts(
                px["view_pos"], px["view_lp"], L, abm.area,
                abm.interaction_range, gspec, grid, f["pos"][row_order],
                row_order, sender[row_order],
                neighbors.chunk_entries(abm.mem_budget_mb))
            counts = jnp.zeros((C, L), jnp.int32).at[row_order].set(out)
            grid_overflow = grid["overflow"]
        else:
            me = jax.lax.axis_index("lp")
            my_idx = me * C + jnp.arange(C, dtype=jnp.int32)
            counts = neighbors.rows_dense_counts(
                px["pos_g"], px["lp_g"], L, abm.area, abm.interaction_range,
                f["pos"], my_idx, sender)
            grid_overflow = jnp.bool_(False)
        return dict(px, counts=counts, grid_overflow=grid_overflow)

    def ph_workload(px):
        # 3c. epidemic diffusion: mirror of engine.ph_workload over the
        # halo view — exposure is one more 2-class candidate walk (the
        # shipped `view_eis` labels stand in for the oracle's id-order
        # label array), and the SI/SIS transition rides full-size
        # id-order draws gathered by SE id, so a row transitions on the
        # same randomness wherever it is hosted (bit-identity)
        f = dict(px["f"])
        valid, safe_gid = px["valid"], px["safe_gid"]
        epi = f["epi"]
        qmask = valid & (epi == 0)
        if spec.grid is not None:
            gspec = spec.grid
            ncells = gspec.ncell * gspec.ncell
            grid = neighbors.build_grid(px["view_pos"], gspec,
                                        valid=px["view_lp"] >= 0,
                                        with_table=False)
            row_order = jnp.argsort(jnp.where(valid, px["cellC"], ncells),
                                    stable=True).astype(jnp.int32)
            out = neighbors.rows_grid_counts(
                px["view_pos"], px["view_eis"], 2, abm.area,
                abm.interaction_range, gspec, grid, f["pos"][row_order],
                row_order, qmask[row_order],
                neighbors.chunk_entries(abm.mem_budget_mb))
            exposure = jnp.zeros((C, 2), jnp.int32).at[row_order].set(
                out)[:, 1]
            ovf = grid["overflow"]
        else:
            me = jax.lax.axis_index("lp")
            my_idx = me * C + jnp.arange(C, dtype=jnp.int32)
            exposure = neighbors.rows_dense_counts(
                px["pos_g"], px["eis_g"], 2, abm.area,
                abm.interaction_range, f["pos"], my_idx, qmask)[:, 1]
            ovf = jnp.bool_(False)
        draws = epidemic_draws(jax.random.wrap_key_data(px["k_move"]),
                               n, abm)
        my_draws = {k: v[safe_gid] for k, v in draws.items()}
        new_epi = epidemic_row_update(epi, exposure, my_draws, abm)
        f["epi"] = jnp.where(valid, new_epi, f["epi"])
        infected = jax.lax.psum(((f["epi"] > 0) & valid).sum(), "lp")
        return dict(px, f=f, infected=infected,
                    grid_overflow=px["grid_overflow"] | ovf)

    def ph_account(px):
        # 3b. communication accounting: the per-pair flow matrix is
        # integer, so the cross-shard psum is exactly the oracle's
        # id-order scatter-add, and the scalar LCR terms derive from it
        # (single source of truth, same as engine.step). Rows of invalid
        # slots are zero (non-senders); their safe_lp=0 rows add nothing.
        f = px["f"]
        safe_lp = jnp.clip(f["lp"], 0, L - 1)
        flows = jax.lax.psum(
            jnp.zeros((L, L), jnp.int32).at[safe_lp].add(px["counts"]),
            "lp")
        local = jnp.trace(flows)
        total = flows.sum()
        return dict(px, safe_lp=safe_lp, flows=flows, local=local,
                    total=total, remote=total - local,
                    migs=jnp.int32(0), n_evals=jnp.int32(0),
                    mig_flows=jnp.zeros((L, L), jnp.int32),
                    reparts=jnp.int32(0))

    def ph_repartition(px):
        # mirror of engine.step's hook: reconstruct the id-order
        # positions (a gather the sparse halo no longer performs), run
        # the *same* partition function on every device, and take this
        # shard's rows back — bit-identity with the oracle by
        # construction, like the mobility models. The gathers (a
        # collective, so they may not live inside the cond) run every
        # step; the reconstruction + partition math fires on
        # repartition steps.
        from repro.core.engine import REPART_SALT
        f = dict(px["f"])
        valid, safe_gid, safe_lp = px["valid"], px["safe_gid"], px["safe_lp"]
        t = px["t"]
        pcfg = part.from_engine(cfg)
        if "gid_all" in px:
            gid_all = px["gid_all"]  # gid rode the flock gather
        else:
            gid_all = jax.lax.all_gather(f["gid"], "lp", axis=0, tiled=True)
        rep_pos = jax.lax.all_gather(f["pos"], "lp", axis=0, tiled=True)
        rep_lp = None
        if part.uses_prev(pcfg):
            # hysteresis backends read the current id-order map too; the
            # gather (a collective: outside the cond) is only paid — and
            # only priced — when the backend actually consumes it
            rep_lp = jax.lax.all_gather(f["lp"], "lp", axis=0, tiled=True)
        k_rep = jax.random.fold_in(jax.random.wrap_key_data(px["k_move"]),
                                   REPART_SALT)
        do = (t > 0) & (t % cfg.repartition_every == 0)

        def _recompute():
            tgt = jnp.where(gid_all >= 0, gid_all, n)  # pads -> dropped
            pos_n = jnp.zeros((n, 2), f["pos"].dtype).at[tgt].set(
                rep_pos, mode="drop")
            prev = None
            if rep_lp is not None:
                # every live SE appears in the gather, so the scatter
                # rebuilds exactly the oracle's `lp` (bit-identity)
                prev = jnp.full((n,), -1, jnp.int32).at[tgt].set(
                    rep_lp, mode="drop")
            # open world: dead ids carry zero weight (and zero position —
            # the oracle zeroes them too, so both layers feed the
            # partitioner byte-identical inputs)
            weights = jnp.zeros((n,), jnp.float32).at[tgt].set(
                1.0, mode="drop") if cfg.open_world else \
                jnp.ones((n,), jnp.float32)
            new_lp_n = part.partition(k_rep, pos_n, weights, pcfg,
                                      prev=prev)
            return new_lp_n[safe_gid]

        new_lp = jax.lax.cond(do, _recompute, lambda: f["lp"])
        move = valid & (new_lp != f["lp"]) & (f["pending_dst"] < 0)
        f["pending_dst"] = jnp.where(move, new_lp, f["pending_dst"])
        f["pending_eta"] = jnp.where(move, t + cfg.migration_delay,
                                     f["pending_eta"])
        f["last_mig"] = jnp.where(move, t, f["last_mig"])
        reparts = jax.lax.psum(move.sum(), "lp")
        mig_flows = px["mig_flows"] + jax.lax.psum(
            jnp.zeros((L, L), jnp.int32).at[safe_lp, new_lp].add(
                move.astype(jnp.int32)), "lp")
        return dict(px, f=f, reparts=reparts, migs=px["migs"] + reparts,
                    mig_flows=mig_flows)

    def ph_heuristic(px):
        # 4/5. self-clustering: window update + evaluation are
        # row-local; the balancer's inputs are psum'd so every device
        # sees the same grants and the per-pair selection stays
        # shard-local (a pair's candidates all live on the shard owning
        # the source LP)
        f = dict(px["f"])
        valid, safe_lp, t = px["valid"], px["safe_lp"], px["t"]
        hstate = {k: f[k] for k in ("ring", "ptr", "since_eval",
                                    "last_mig")}
        hstate = heu.update_window(cfg.heuristic, hstate, px["counts"],
                                   px["sender"], t)
        cand, dest, alpha, hstate, n_eval_loc = heu.evaluate(
            cfg.heuristic, hstate, f["lp"], t, valid=valid, mf=px["mf"])
        n_evals = jax.lax.psum(n_eval_loc, "lp")
        cand = cand & (f["pending_dst"] < 0)
        cmat = jax.lax.psum(bal.candidate_matrix(cand, safe_lp, dest, L),
                            "lp")
        if cfg.balance == "asymmetric":
            cap_sh = jnp.asarray(cfg.effective_capacity(), jnp.float32)
            current = jax.lax.psum(
                jnp.bincount(jnp.where(valid, f["lp"], L),
                             length=L + 1)[:L], "lp")
            grants = bal.asymmetric_grants(cmat, current, cap_sh)
        else:
            grants = bal.symmetric_grants(cmat)
        admit = bal.select_migrations(cand, safe_lp, dest, alpha, grants,
                                      L, tiebreak=f["gid"])
        f["pending_dst"] = jnp.where(admit, dest, f["pending_dst"])
        f["pending_eta"] = jnp.where(admit, t + cfg.migration_delay,
                                     f["pending_eta"])
        hstate = dict(hstate,
                      last_mig=jnp.where(admit, t, hstate["last_mig"]))
        f.update(hstate)
        migs = px["migs"] + jax.lax.psum(admit.sum(), "lp")
        mig_flows = px["mig_flows"] + jax.lax.psum(
            jnp.zeros((L, L), jnp.int32).at[safe_lp, dest].add(
                admit.astype(jnp.int32)), "lp")
        return dict(px, f=f, n_evals=n_evals, migs=migs,
                    mig_flows=mig_flows)

    def ph_finalize(px):
        me = jax.lax.axis_index("lp")
        f = dict(px["f"])
        valid, wire = px["valid"], px["wire"]
        grb = _gather_row_bytes(cfg)
        if grb and D > 1:
            # id-order reconstruction gathers (flock / repartition):
            # their valid rows are real row payload, priced like the
            # halo rows (integer add — placement after the heuristic
            # phase leaves the sum exactly the historical value)
            vcnt = jax.lax.all_gather(px["n_valid"], "lp")  # (D,)
            wire = wire + (vcnt[:, None] * grb
                           * (1 - jnp.eye(D, dtype=jnp.int32)))

        # 6. negotiate step t+1's halo on step t's tail (the double
        # buffer): each device contributes its post-mobility occupancy
        # plus the cells of rows pending toward each destination, psum
        # ORs the bitmaps, and the dilation (3x3 + one step of motion)
        # makes the stale footprint a sound superset of tomorrow's true
        # need. This is the only global agreement the exchange requires,
        # and it overlaps this step's compute instead of stalling the
        # next step's head.
        if _sparse_halo(spec):
            nc = spec.grid.ncell
            ncells = nc * nc
            pend = valid & (f["pending_dst"] >= 0)
            pdev = dev_of_lp(jnp.maximum(f["pending_dst"], 0), spec)
            safe_cell = jnp.where(valid, px["cellC"], ncells)
            contrib = jnp.zeros((D, ncells), bool)
            contrib = contrib.at[jnp.full((C,), me), safe_cell].set(
                True, mode="drop")
            contrib = contrib.at[jnp.where(pend, pdev, D), safe_cell].set(
                True, mode="drop")
            occ_all = jax.lax.psum(contrib.astype(jnp.int32), "lp") > 0
            f["halo_need"] = neighbors.dilate_mask(
                occ_all.reshape(D, nc, nc),
                _dilation_radius(spec, abm)).reshape(D, ncells)

        local, total = px["local"], px["total"]
        halo_total = jax.lax.psum(px["halo_n"], "lp").astype(jnp.float32)
        remote_slots = ((D - 1) * px["all_valid"]).astype(jnp.float32)
        overflow = jax.lax.psum(
            (px["reshard_overflow"] | px["grid_overflow"]
             | px["halo_overflow"]).astype(jnp.int32), "lp")
        metrics = {
            "local_msgs": local.astype(jnp.float32),
            "remote_msgs": px["remote"].astype(jnp.float32),
            "migrations": px["migs"].astype(jnp.float32),
            "heu_evals": px["n_evals"].astype(jnp.float32),
            "lcr": local.astype(jnp.float32)
                   / jnp.maximum(total.astype(jnp.float32), 1.0),
            "lp_flows": px["flows"],
            "mig_flows": px["mig_flows"],
            "repartitions": px["reparts"].astype(jnp.float32),
            # mean remote agents a shard actually needs (its halo), as a
            # fraction of all remote agents — GAIA's clustering drives
            # this down, and the sparse exchange realizes the saving on
            # the wire
            "halo_frac": halo_total / jnp.maximum(remote_slots, 1.0),
            # exact per-step bytes of useful row payload exchanged
            # (packed halo rows + admitted cross-device migrations +
            # id-order reconstruction gathers); wire_flows is its per
            # device-pair breakdown, priced by costmodel.wct_env
            "bytes_on_wire": wire.sum().astype(jnp.float32),
            "wire_flows": wire,
            "shard_overflow": (overflow > 0).astype(jnp.float32),
        }
        if cfg.open_world:
            # live population (post-arrival), mirroring engine.step
            metrics["pop"] = px["all_valid"].astype(jnp.float32)
        if abm.workload == "epidemic":
            metrics["infected"] = px["infected"].astype(jnp.float32)
        return dict(px, f=f, metrics=metrics)

    phases = [("migrate", ph_migrate), ("mobility", ph_mobility),
              ("halo_exchange", ph_halo), ("proximity", ph_proximity),
              ("accounting", ph_account)]
    if abm.workload == "epidemic":
        phases.insert(4, ("workload", ph_workload))
    if cfg.repartition_every > 0:
        phases.append(("repartition", ph_repartition))
    if cfg.gaia_on:
        phases.append(("heuristic", ph_heuristic))
    phases.append(("finalize", ph_finalize))
    return phases


def _shard_step(f, k_move, k_send, t, mf, cfg, spec: ShardSpec):
    """Per-device body of one timestep (runs under shard_map). `mf` is
    the dynamic Migration Factor (see engine.run_window). The body is
    the fused composition of `_sharded_phases`; named scopes annotate
    profiler timelines without adding ops."""
    px = {"f": f, "k_move": k_move, "k_send": k_send, "t": t, "mf": mf}
    for name, fn in _sharded_phases(cfg, spec):
        with jax.named_scope(f"step.{name}"):
            px = fn(px)
    return px["f"], px["metrics"]


_FIELD_SPECS = {
    "pos": P("lp"), "waypoint": P("lp"), "mob": P("lp"),
    "mob_g": P(),  # global mobility rows: replicated on every device
    "lp": P("lp"), "gid": P("lp"), "epi": P("lp"),
    "pending_dst": P("lp"), "pending_eta": P("lp"), "ring": P(None, "lp"),
    "ptr": P("lp"), "since_eval": P("lp"), "last_mig": P("lp"),
}

_METRIC_SPECS = {k: P() for k in
                 ("local_msgs", "remote_msgs", "migrations", "heu_evals",
                  "lcr", "lp_flows", "mig_flows", "repartitions",
                  "halo_frac", "bytes_on_wire", "wire_flows",
                  "shard_overflow")}


def _field_specs(spec: ShardSpec):
    """Per-SE field specs for this layout; the sparse halo adds the
    replicated `halo_need` double buffer to the carried state."""
    specs = dict(_FIELD_SPECS)
    if _sparse_halo(spec):
        specs["halo_need"] = P()
    return specs


def _metric_specs(cfg):
    """Metric output specs: open-world runs add the `pop` series,
    epidemic runs the `infected` series."""
    specs = dict(_METRIC_SPECS)
    if cfg.open_world:
        specs["pop"] = P()
    if cfg.abm.workload == "epidemic":
        specs["infected"] = P()
    return specs


def _batch_field_specs(spec: ShardSpec):
    """Batched replicas: a leading (unsharded) replica axis in front of
    every per-SE field's spec — the "lp" mesh axis keeps sharding the
    slot dimension, replicas ride along inside each shard."""
    return {k: P(None, *v) for k, v in _field_specs(spec).items()}


def step_sharded(state, cfg, spec: ShardSpec, mesh: Mesh, mf=None):
    """One sharded timestep. Same contract as `engine.step`, on
    slot-major state; metrics additionally report halo_frac,
    bytes_on_wire, wire_flows and shard_overflow."""
    if mf is None:
        mf = jnp.float32(cfg.heuristic.mf)
    key, k_move, k_send = jax.random.split(state["key"], 3)
    fspecs = _field_specs(spec)
    fields = {k: state[k] for k in fspecs}
    fn = jax.shard_map(
        partial(_shard_step, cfg=cfg, spec=spec),
        mesh=mesh,
        in_specs=(fspecs, P(), P(), P(), P()),
        out_specs=(fspecs, _metric_specs(cfg)),
        check_vma=False,  # psum'd outputs are replicated by construction
    )
    new_fields, metrics = fn(fields, jax.random.key_data(k_move),
                             jax.random.key_data(k_send), state["t"], mf)
    new_state = dict(new_fields, key=key, t=state["t"] + 1)
    return new_state, metrics


def step_sharded_batch(state, cfg, spec: ShardSpec, mesh: Mesh, mfs):
    """One timestep of R stacked replicas: `jax.vmap` of the per-device
    body *inside* `shard_map`, so each device advances its shard of all
    R replicas in one pass and the collectives batch across the replica
    axis. Because the vmapped body is the very `_shard_step` the
    single-replica path runs, per-seed bit-identity with the oracle is
    inherited rather than re-proven (tests/test_replicas.py). `mfs` is
    the (R,) per-replica Migration Factor vector."""
    ks = jax.vmap(lambda k: jax.random.split(k, 3))(state["key"])
    key, k_move, k_send = ks[:, 0], ks[:, 1], ks[:, 2]
    fspecs = _field_specs(spec)
    fields = {k: state[k] for k in fspecs}
    fn = jax.shard_map(
        jax.vmap(partial(_shard_step, cfg=cfg, spec=spec),
                 in_axes=(0, 0, 0, 0, 0)),
        mesh=mesh,
        in_specs=(_batch_field_specs(spec), P(), P(), P(), P()),
        out_specs=(_batch_field_specs(spec), _metric_specs(cfg)),
        check_vma=False,
    )
    new_fields, metrics = fn(fields, jax.random.key_data(k_move),
                             jax.random.key_data(k_send), state["t"], mfs)
    return dict(new_fields, key=key, t=state["t"] + 1), metrics


# ---------------------------------------------------------------------------
# open-world churn ops (mirror engine.oracle_arrive / oracle_depart)
# ---------------------------------------------------------------------------


def _vacate_slots(f, hit):
    """Free the slots in `hit`: gid = lp = -1 plus a full slot-history
    reset (ring included, matching engine.oracle_depart — a reused slot
    carries nothing of its previous occupant)."""
    f = dict(f)
    f["gid"] = jnp.where(hit, -1, f["gid"])
    f["lp"] = jnp.where(hit, -1, f["lp"])
    f["pending_dst"] = jnp.where(hit, -1, f["pending_dst"])
    f["pending_eta"] = jnp.where(hit, -1, f["pending_eta"])
    f["last_mig"] = jnp.where(hit, -10**6, f["last_mig"])
    f["ptr"] = jnp.where(hit, 0, f["ptr"])
    f["since_eval"] = jnp.where(hit, 0, f["since_eval"])
    f["epi"] = jnp.where(hit, 0, f["epi"])
    f["ring"] = jnp.where(hit[None, :, None], 0, f["ring"])
    return f


def _shard_depart(f, ids, spec: ShardSpec):
    """Per-device body: vacate the slots holding global ids `ids`
    ((B,) replicated; -1 = padding). Returns (fields, found) with
    `found` the psum'd (B,) per-id located mask — the facade's
    exact-or-loud check against the requested batch."""
    eq = (f["gid"][:, None] == ids[None, :]) & (f["gid"] >= 0)[:, None]
    hit = eq.any(axis=1)
    found = jax.lax.psum(eq.any(axis=0).astype(jnp.int32), "lp") > 0
    return _vacate_slots(f, hit), found


def _shard_arrive(f, ids, pos, wp, mob, epi, lps, cfg, spec: ShardSpec):
    """Per-device body: insert B SEs (all args replicated; ids = -1 is
    padding). Each device claims the arrivals whose destination LP it
    owns and packs them into its free slots in ascending-slot order.
    Returns (fields, admitted): refusals (no free slot on the owning
    device) write nothing, and `admitted` is the psum'd (B,) per-arrival
    mask — the facade raises on any refusal, exact-or-loud, naming
    shard_capacity. Admitted arrival cells are OR'd (dilated) into the
    owning device's halo-need bitmap so the very next step's exchange
    already covers them."""
    me = jax.lax.axis_index("lp")
    real = ids >= 0
    mine = real & (dev_of_lp(jnp.maximum(lps, 0), spec) == me)
    free = f["gid"] < 0
    free_order = jnp.argsort(~free, stable=True)  # free slots first, asc
    arr_rank = jnp.cumsum(mine) - 1
    admitted = mine & (arr_rank < free.sum())
    target = jnp.where(admitted,
                       free_order[jnp.clip(arr_rank, 0, spec.cap - 1)],
                       spec.cap)

    f = dict(f)
    f["pos"] = f["pos"].at[target].set(pos, mode="drop")
    f["waypoint"] = f["waypoint"].at[target].set(wp, mode="drop")
    f["mob"] = f["mob"].at[target].set(mob, mode="drop")
    f["epi"] = f["epi"].at[target].set(epi, mode="drop")
    f["gid"] = f["gid"].at[target].set(ids, mode="drop")
    f["lp"] = f["lp"].at[target].set(lps, mode="drop")
    f["pending_dst"] = f["pending_dst"].at[target].set(-1, mode="drop")
    f["pending_eta"] = f["pending_eta"].at[target].set(-1, mode="drop")
    f["ring"] = f["ring"].at[:, target, :].set(0, mode="drop")
    f["ptr"] = f["ptr"].at[target].set(0, mode="drop")
    f["since_eval"] = f["since_eval"].at[target].set(0, mode="drop")
    f["last_mig"] = f["last_mig"].at[target].set(-10**6, mode="drop")
    adm = jax.lax.psum(admitted.astype(jnp.int32), "lp") > 0

    if _sparse_halo(spec):
        # the negotiated need bitmaps predate this arrival; OR its
        # dilated cell into the owner's footprint so step t+1's exchange
        # is sound without waiting a step (departures only shrink the
        # true need, so their stale superset stays sound untouched)
        g = spec.grid
        ncells = g.ncell * g.ncell
        cell = neighbors.cell_ids(pos, g)
        contrib = jnp.zeros((spec.n_dev, ncells), bool)
        dev = dev_of_lp(jnp.maximum(lps, 0), spec)
        contrib = contrib.at[jnp.where(real, dev, spec.n_dev),
                             cell].set(True, mode="drop")
        contrib = jax.lax.psum(contrib.astype(jnp.int32), "lp") > 0
        f["halo_need"] = f["halo_need"] | neighbors.dilate_mask(
            contrib.reshape(spec.n_dev, g.ncell, g.ncell),
            _dilation_radius(spec, cfg.abm)).reshape(spec.n_dev, ncells)
    return f, adm


@functools.lru_cache(maxsize=COMPILED_CACHE_SIZE)
def _compiled_depart_sharded(key_cfg):
    spec = make_shard_spec(key_cfg)
    mesh = make_mesh(spec)
    fspecs = _field_specs(spec)
    fn = jax.shard_map(partial(_shard_depart, spec=spec), mesh=mesh,
                       in_specs=(fspecs, P()), out_specs=(fspecs, P()),
                       check_vma=False)
    return jax.jit(fn), spec


@functools.lru_cache(maxsize=COMPILED_CACHE_SIZE)
def _compiled_arrive_sharded(key_cfg):
    spec = make_shard_spec(key_cfg)
    mesh = make_mesh(spec)
    fspecs = _field_specs(spec)
    fn = jax.shard_map(partial(_shard_arrive, cfg=key_cfg, spec=spec),
                       mesh=mesh,
                       in_specs=(fspecs, P(), P(), P(), P(), P(), P()),
                       out_specs=(fspecs, P()), check_vma=False)
    return jax.jit(fn), spec


def depart_sharded(state, cfg, ids):
    """Vacate the slots of global ids `ids` (-1 = padding). Returns
    (state, found): the (B,) per-id located mask."""
    from repro.core.engine import strip_obs, window_key_cfg
    fn, spec = _compiled_depart_sharded(window_key_cfg(strip_obs(cfg)))
    fields = {k: state[k] for k in _field_specs(spec)}
    new_fields, found = fn(fields, jnp.asarray(ids, jnp.int32))
    return dict(new_fields, key=state["key"], t=state["t"]), found


def arrive_sharded(state, cfg, ids, rows):
    """Insert SEs with global ids `ids` (-1 = padding) into free slots
    of the devices owning rows["lp"]. Returns (state, admitted): the
    (B,) per-arrival admission mask — refused arrivals wrote nothing
    (see Engine.arrive for the loud path)."""
    from repro.core.engine import strip_obs, window_key_cfg
    fn, spec = _compiled_arrive_sharded(window_key_cfg(strip_obs(cfg)))
    fields = {k: state[k] for k in _field_specs(spec)}
    pos = jnp.asarray(rows["pos"], jnp.float32)
    new_fields, adm = fn(
        fields, jnp.asarray(ids, jnp.int32), pos,
        jnp.asarray(rows.get("waypoint", pos), jnp.float32),
        jnp.asarray(rows.get("mob", jnp.zeros_like(pos)), jnp.float32),
        jnp.asarray(rows.get("epi", jnp.zeros(pos.shape[:1], jnp.int32)),
                    jnp.int32),
        jnp.asarray(rows["lp"], jnp.int32))
    return dict(new_fields, key=state["key"], t=state["t"]), adm


# ---------------------------------------------------------------------------
# runners (mirror engine.run / engine.run_window)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=COMPILED_CACHE_SIZE)
def _compiled_window_sharded(key_cfg, n_steps: int):
    # mirror of engine._compiled_window: one jitted scan per config
    # shape, MF dynamic (key_cfg comes pre-normalized via
    # engine.window_key_cfg, so MF sweeps share one executable)
    spec = make_shard_spec(key_cfg)
    mesh = make_mesh(spec)

    if not key_cfg.obs.enabled:
        def fn(state, mf):
            def body(s, _):
                return step_sharded(s, key_cfg, spec, mesh, mf=mf)
            state, series = jax.lax.scan(body, state, None, length=n_steps)
            return state, series, window_summary(series)
        return jax.jit(fn)

    # telemetry on: same ring-drain design as engine._compiled_window,
    # living at the jit level *outside* shard_map — the metrics the row
    # reads are psum-replicated and the slot-major state is globally
    # addressable here, so the callback executes once per wrap (not per
    # device) under single-process SPMD
    de = key_cfg.obs.drain_every
    n_cols = len(obs_ledger.ledger_keys(key_cfg))

    def fn(state, mf):
        def body(carry, _):
            s, ring = carry
            s2, m = step_sharded(s, key_cfg, spec, mesh, mf=mf)
            t = s["t"]
            ring = ring.at[t % de].set(
                obs_ledger.ledger_row(key_cfg, s2, m, t))
            jax.lax.cond(
                (t + 1) % de == 0,
                lambda r, tt: jax.debug.callback(obs_runtime.on_block,
                                                 r, tt, ordered=False),
                lambda r, tt: None,
                ring, t)
            return (s2, ring), m
        ring0 = jnp.full((de, n_cols), -1.0, jnp.float32)
        (s, ring), series = jax.lax.scan(body, (state, ring0), None,
                                         length=n_steps)
        return s, ring, series, window_summary(series)
    return jax.jit(fn)


def _scan_sharded(state, cfg, n_steps: int, mf=None):
    """Returns (state, series, summary), as `engine._dispatch_window`."""
    from repro.core.engine import window_key_cfg
    mf_val = jnp.float32(cfg.heuristic.mf if mf is None else mf)
    if cfg.obs.enabled:
        t0 = int(state["t"])
        state, ring, series, summary = _compiled_window_sharded(
            window_key_cfg(cfg), n_steps)(state, mf_val)
        obs_runtime.flush_tail(ring, t0, t0 + n_steps)
        return state, series, summary
    return _compiled_window_sharded(window_key_cfg(cfg), n_steps)(
        state, mf_val)


def walk_slots(cfg) -> int:
    """Candidate slots the proximity walks of one sharded step visit,
    summed over the devices: each device walks its `cap` local slots,
    through the grid walk over its halo view or, without a grid, the
    dense row sweep against every slot of the mesh."""
    from repro.core.engine import window_key_cfg
    spec = make_shard_spec(window_key_cfg(cfg))
    if spec.grid is not None:
        per_dev = neighbors.grid_walk(
            spec.cap, spec.grid.capacity,
            neighbors.chunk_entries(cfg.abm.mem_budget_mb))[2]
    else:
        rows = -(-spec.cap // neighbors.DENSE_CHUNK) * neighbors.DENSE_CHUNK
        per_dev = rows * spec.n_slots
    return spec.n_dev * per_dev


def run_sharded(key, cfg):
    """Sharded mirror of `engine.run`: returns (final_state, series,
    counters) with the final state unsharded back to gid-order, so
    callers (and the equivalence tests) see the oracle's layout."""
    from repro.core.engine import _migration_ratio, series_shapes, unpack
    spec = make_shard_spec(cfg)
    st = init_sharded(key, cfg, spec)
    st, series, summary = _scan_sharded(st, cfg, cfg.timesteps)
    counters = unpack(summary, series_shapes(series))
    counters["migration_ratio"] = _migration_ratio(counters, cfg)  # Eq. 8
    return unshard_state(st, spec), series, counters


# ---------------------------------------------------------------------------
# batched multi-replica runners (mirror engine.run_batch/run_window_batch)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=COMPILED_CACHE_SIZE)
def _compiled_batch_sharded(key_cfg, n_steps: int):
    # mirror of engine._compiled_batch: one jitted batched scan per
    # config shape, per-replica MF dynamic (jit re-specializes per
    # replica count)
    spec = make_shard_spec(key_cfg)
    mesh = make_mesh(spec)

    def fn(state, mfs):
        def body(s, _):
            return step_sharded_batch(s, key_cfg, spec, mesh, mfs)
        state, series = jax.lax.scan(body, state, None, length=n_steps)
        return state, series, batch_summary(series)
    return jax.jit(fn)


def _scan_batch_sharded(states, cfg, n_steps: int, mf=None):
    # batched scans are un-instrumented (strip_obs): the ledger covers
    # the single-replica resident paths
    from repro.core.engine import _mf_vector, strip_obs, window_key_cfg
    n_rep = states["t"].shape[0]
    return _compiled_batch_sharded(window_key_cfg(strip_obs(cfg)), n_steps)(
        states, _mf_vector(cfg, mf, n_rep))


def unshard_batch(states, spec: ShardSpec):
    """Unshard each replica of a stacked slot-major state back to the
    oracle's gid-order layout (stacked again on the replica axis)."""
    from repro.core.engine import stack_states
    n_rep = states["t"].shape[0]
    return stack_states([
        unshard_state({k: v[r] for k, v in states.items()}, spec)
        for r in range(n_rep)])


def run_batch_sharded(cfg, seeds):
    """Sharded mirror of `engine.run_batch`: R replicas vmapped inside
    each shard, final states unsharded to gid-order per replica — so
    sharded replicas compare byte-for-byte against oracle replicas."""
    from repro.core.engine import (_migration_ratio, replica_keys,
                                   series_shapes, stack_states,
                                   unpack_batch)
    spec = make_shard_spec(cfg)
    states = stack_states([init_sharded(k, cfg, spec)
                           for k in replica_keys(seeds)])
    states, series, summary = _scan_batch_sharded(states, cfg,
                                                  cfg.timesteps)
    reps = unpack_batch(summary, series_shapes(series))
    for c in reps:
        c["migration_ratio"] = _migration_ratio(c, cfg)  # Eq. 8
    return unshard_batch(states, spec), series, reps
