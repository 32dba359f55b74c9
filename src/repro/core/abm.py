"""The paper's evaluation model (§5.1): an agent-based model on a toroidal
2-D space. Agents interact by proximity: each sender's interaction
reaches every agent within the threshold range.

Mobility is pluggable (`ABMConfig.mobility` — the paper's claim is that
self-clustering pays off across "various configurations of the
simulation model", so the workloads must go beyond uniform RWP):

  "rwp"      Random Waypoint (min speed = max speed, sleep 0, Exp. 1).
             Near-uniform stationary density — the friendliest case.
  "hotspot"  K moving attractors (themselves doing RWP); SEs are pulled
             toward their attractor with per-step noise. Sustained
             non-uniform density: K dense blobs wandering the torus.
  "group"    RPGM-style group mobility: K leader points do RWP, each SE
             chases (leader + its fixed member offset). Groups migrate
             coherently across the space.
  "flock"    flocking-lite: each SE steers by alignment + cohesion
             toward the centroid/mean-heading of its 3x3 cell-list
             neighborhood (reusing the proximity grid geometry), plus
             noise. Clusters *emerge* instead of being imposed.
  "trace"    trace replay: positions come frame-by-frame from a
             registered GPS/taxi-style trace (repro.data.pipeline —
             `register_trace`, `synthetic_trace`, `resample_trace`).
             Step t replays frame t+1 (frame 0 is the initial state);
             when the trace is shorter than the horizon,
             `trace_policy` picks loop / hold-last / exact-or-raise.
             Consumes no PRNG and is row-local, so the sharded engine
             replays it gather-free and bit-identically.

Orthogonally to *where SEs move*, `ABMConfig.workload` adds a model of
*what they compute*: "epidemic" spreads an SI/SIS infection flag (the
`epi` state field) over the proximity graph each step — susceptible
SEs catch with p = 1-(1-beta)^exposure from in-range infectious
senders, infectious SEs interact `epi_boost`x more often — so event
load follows the infection wave instead of the density map. That is
the dynamic-load regime (Kurve et al., Boulmier et al.) pure mobility
cannot produce, and the reason GAIA's self-clustering is stressed by
it.

Every model is a pure function of (key, state) in global-SE-id order, so
the sharded engine reproduces it bit-exactly wherever an SE is hosted
(see parallel/lp_shard.py). Per-SE mobility state lives in two fields
that travel with the SE: `waypoint` (rwp target) and `mob` (member
offset for "group", unit heading for "flock"); global mobility state
(attractor/leader rows) lives in `mob_g`, replicated everywhere.

Vectorized over all SEs. The proximity/LP-histogram hot spot — the O(N^2)
pairwise matching the paper names as the model's dominant cost — has four
interchangeable backends selected by `ABMConfig.proximity_backend`:

  "dense"        full O(N^2) jnp sweep; the exact-parity oracle
  "grid"         cell-list neighbor search (core/neighbors.py), O(N*k);
                 the default — bit-identical to dense
  "pallas"       dense-sweep Pallas TPU kernel (kernels/proximity)
  "pallas_grid"  grid-candidate Pallas TPU kernel (kernels/proximity)

All four return bit-identical counts (tests/test_neighbors.py); "grid"
and "pallas_grid" fall back to the dense math when the world is too
small to tessellate (area / interaction_range < 3 cells per side).
Non-uniform mobility breaks the grid's uniform-density auto-capacity:
`grid_spec()` switches to a clustered-density bound for the non-RWP
models (see neighbors.clustered_capacity), and the engine surfaces the
per-step `grid_overflow` metric so runs can assert exactness.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import neighbors
from repro.core import partition as part

PROXIMITY_BACKENDS = ("dense", "grid", "pallas", "pallas_grid")
MOBILITY_MODELS = ("rwp", "hotspot", "group", "flock", "trace")
WORKLOADS = ("none", "epidemic")
TRACE_POLICIES = ("loop", "hold", "exact")

#: PRNG salts of the epidemic workload's independent streams (fold_in
#: off the step key, like the repartition/init salts — the epidemic
#: consumes no draw any existing stream sees, so workload="none" runs
#: stay bit-identical to pre-epidemic seeds)
EPI_SEED_SALT = 0x390a
EPI_INFECT_SALT = 0x3911
EPI_RECOVER_SALT = 0x3912

#: attractor ("hotspot") / leader ("group") speed relative to SE speed —
#: slower than the SEs chasing them, so clusters stay coherent in motion
_GLOBAL_SPEED_FACTOR = 0.5


@dataclasses.dataclass(frozen=True)
class ABMConfig:
    n_se: int = 10_000
    n_lp: int = 4
    area: float = 10_000.0  # toroidal square side (spaceunits)
    speed: float = 11.0  # spaceunits/timestep (min = max, Exp. 1)
    interaction_range: float = 250.0
    p_interact: float = 0.2  # pi: P(SE sends an interaction this timestep)
    proximity_backend: str = "grid"  # see PROXIMITY_BACKENDS
    grid_capacity: int = 0  # per-cell member cap; 0 = auto from density
    # hard memory budget (MiB) for the proximity data structures: sizes
    # the proximity sweeps' chunk transients and clamps the auto grid
    # capacity (neighbors.budget_capacity). 0 = unbudgeted (historical
    # defaults). A budget too small for the true density is loud, never
    # silent: the clamped capacity trips `grid_overflow`, exactness is
    # re-checkable.
    mem_budget_mb: int = 0
    # --- mobility scenario (see module docstring) -----------------------
    mobility: str = "rwp"  # see MOBILITY_MODELS
    n_groups: int = 8  # K attractors ("hotspot") / groups ("group")
    group_radius: float = 250.0  # cluster spatial scale (spaceunits)
    # --- trace replay (mobility == "trace") -----------------------------
    # the trace itself is data, not config: `trace_name` keys into the
    # repro.data.pipeline registry so this dataclass stays hashable for
    # the compiled-scan memo; frames become jit constants at trace time
    trace_name: str = ""
    trace_policy: str = "loop"  # see TRACE_POLICIES
    # --- interacting workload (see module docstring) --------------------
    workload: str = "none"  # see WORKLOADS
    epi_beta: float = 0.3  # per-contact per-step infection probability
    epi_gamma: float = 0.0  # per-step recovery probability (0=SI, >0=SIS)
    epi_seed_frac: float = 0.02  # initially infectious fraction (a patch)
    epi_boost: float = 4.0  # send-probability multiplier while infectious
    # --- initial SE -> LP map (core/partition.py registry) --------------
    partitioner: str = "random"  # see partition.PARTITION_BACKENDS
    # REMOVED (was a PR 1 boolean, deprecated since PR 1/PR 5): passing
    # it raises a TypeError naming `proximity_backend`. An InitVar keeps
    # the keyword accepted long enough to fail with that message instead
    # of dataclasses' generic "unexpected keyword argument".
    use_pallas: dataclasses.InitVar[object] = None

    def __post_init__(self, use_pallas=None):
        if use_pallas is not None:
            raise TypeError(
                "ABMConfig.use_pallas was removed; set "
                "proximity_backend='pallas' (or 'pallas_grid') instead")
        if self.proximity_backend not in PROXIMITY_BACKENDS:
            raise ValueError(
                f"proximity_backend={self.proximity_backend!r} not in "
                f"{PROXIMITY_BACKENDS}")
        if self.partitioner not in part.PARTITION_BACKENDS:
            raise ValueError(
                f"partitioner={self.partitioner!r} not in "
                f"{part.PARTITION_BACKENDS}")
        if self.mobility not in MOBILITY_MODELS:
            raise ValueError(
                f"mobility={self.mobility!r} not in {MOBILITY_MODELS}")
        if self.mobility in ("hotspot", "group") and self.n_groups < 1:
            raise ValueError("n_groups must be >= 1 for clustered mobility")
        if self.n_se < 1 or self.n_lp < 1:
            raise ValueError(
                f"n_se={self.n_se} and n_lp={self.n_lp} must be >= 1")
        if self.area <= 0 or self.interaction_range <= 0:
            raise ValueError(
                f"area={self.area} and interaction_range="
                f"{self.interaction_range} must be > 0")
        if self.speed < 0 or self.group_radius <= 0:
            raise ValueError("speed must be >= 0 and group_radius > 0")
        if not 0.0 <= self.p_interact <= 1.0:
            raise ValueError(
                f"p_interact={self.p_interact} must be a probability")
        if self.grid_capacity < 0 or self.mem_budget_mb < 0:
            raise ValueError(
                "grid_capacity and mem_budget_mb must be >= 0 (0 = auto)")
        if self.mobility == "trace" and not self.trace_name:
            raise ValueError(
                "mobility='trace' needs trace_name — a key registered "
                "via repro.data.pipeline.register_trace")
        if self.trace_policy not in TRACE_POLICIES:
            raise ValueError(
                f"trace_policy={self.trace_policy!r} not in "
                f"{TRACE_POLICIES}")
        if self.workload not in WORKLOADS:
            raise ValueError(
                f"workload={self.workload!r} not in {WORKLOADS}")
        if self.workload == "epidemic":
            if self.proximity_backend not in ("dense", "grid"):
                raise ValueError(
                    "workload='epidemic' implements its exposure sweep "
                    "on the dense/grid proximity backends only")
            for nm, v in (("epi_beta", self.epi_beta),
                          ("epi_gamma", self.epi_gamma)):
                if not 0.0 <= v <= 1.0:
                    raise ValueError(f"{nm}={v} must be a probability")
            if not 0.0 < self.epi_seed_frac <= 1.0:
                raise ValueError(
                    f"epi_seed_frac={self.epi_seed_frac} must be in "
                    "(0, 1]")
            if self.epi_boost < 1.0:
                raise ValueError(
                    f"epi_boost={self.epi_boost} must be >= 1 (1 = no "
                    "load shift)")

    def resolved_backend(self) -> str:
        """The proximity backend (kept for callers of the historical
        `use_pallas`-shim API; the field itself is gone)."""
        return self.proximity_backend

    def grid_spec(self):
        """Cell-list geometry for this config, or None if the world is
        too small to tessellate (grid backends then use dense math).

        An explicit `grid_capacity` always wins (never budget-clamped).
        Otherwise the auto capacity is density-adaptive in two stages:
        the mobility model picks the density bound — RWP keeps the
        uniform Poisson bound; the clustered models size for K blobs of
        n/K SEs at the model's spatial scale (attractor dwell radius /
        member offset radius / a cell for emergent flocks), where the
        uniform bound would overflow and silently undercount — and then
        a positive `mem_budget_mb` clamps it to what the budget affords
        (neighbors.budget_capacity). The clamp keeps the exact-or-loud
        contract: an underbudgeted capacity trips `grid_overflow`."""
        spec = neighbors.make_grid_spec(self.n_se, self.area,
                                        self.interaction_range,
                                        capacity=self.grid_capacity)
        if spec is None or self.grid_capacity > 0:
            return spec
        if self.mobility == "trace":
            # the frames are known in full, so the density bound is not
            # a heuristic: the exact peak cell occupancy over every
            # frame (positions each step ARE a frame, so nothing can
            # exceed it)
            cap = trace_frames(self).peak_cell_occupancy(spec.ncell)
            spec = dataclasses.replace(spec,
                                       capacity=max(spec.capacity, cap))
        elif self.mobility != "rwp":
            radius = {"hotspot": 0.5 * self.group_radius,
                      "group": self.group_radius,
                      "flock": spec.cell}[self.mobility]
            cap = neighbors.clustered_capacity(self.n_se, spec.ncell,
                                               spec.cell, self.n_groups,
                                               radius)
            spec = dataclasses.replace(spec,
                                       capacity=max(spec.capacity, cap))
        if self.mem_budget_mb > 0:
            cap = min(spec.capacity,
                      neighbors.budget_capacity(spec.ncell,
                                                self.mem_budget_mb))
            spec = dataclasses.replace(spec, capacity=cap)
        return spec


def mobility_globals(cfg: ABMConfig) -> int:
    """Rows of the replicated global mobility state `mob_g` (attractors
    for "hotspot", leaders for "group"; 1 row otherwise so shapes stay
    static — "trace" rides its frame counter in that row's [0, 0])."""
    return cfg.n_groups if cfg.mobility in ("hotspot", "group") else 1


def trace_frames(cfg: ABMConfig):
    """Resolve cfg.trace_name to its registered Trace, validated against
    the config (exact-or-loud: a trace of the wrong shape or world size
    would replay garbage silently)."""
    from repro.data import pipeline as dpipe
    tr = dpipe.get_trace(cfg.trace_name)
    if tr.n_se != cfg.n_se:
        raise ValueError(
            f"trace {cfg.trace_name!r} holds {tr.n_se} SEs but "
            f"ABMConfig.n_se={cfg.n_se}")
    if abs(tr.area - cfg.area) > 1e-6 * max(cfg.area, 1.0):
        raise ValueError(
            f"trace {cfg.trace_name!r} lives on an area={tr.area} torus "
            f"but ABMConfig.area={cfg.area}")
    return tr


def check_trace_horizon(cfg: ABMConfig, t0: int, n_steps: int) -> None:
    """Host-side guard for trace_policy='exact': every step of the
    window [t0, t0 + n_steps) must read a real frame (step t replays
    frame t+1). Called by the engine runners before tracing — raising
    here beats silently holding the last frame, which is exactly what
    'exact' exists to forbid."""
    if n_steps <= 0 or cfg.mobility != "trace" \
            or cfg.trace_policy != "exact":
        return
    T = trace_frames(cfg).timesteps
    need = t0 + n_steps  # the last step of the window reads this frame
    if need > T - 1:
        raise ValueError(
            f"trace {cfg.trace_name!r} has {T} frames but steps "
            f"[{t0}, {t0 + n_steps}) need frame {need} under "
            "trace_policy='exact'; shorten the horizon, extend the "
            "trace, or pick trace_policy='loop'/'hold'")


def init_abm(key, cfg: ABMConfig):
    """Initial model state, in global-SE-id order.

    Besides pos/waypoint/lp this now carries the mobility state: `mob`
    (N, 2) per-SE (member offsets / headings; zeros when unused) and
    `mob_g` (G, 4) global rows [pos | waypoint] for attractors/leaders.
    The k1/k2/k3 consumption is unchanged from the RWP-only version, so
    existing RWP seeds reproduce bit-identically; clustered models remap
    the same k1 uniforms into their blob offsets (initial density is
    non-uniform from step 0, which is the point of those scenarios).

    The SE -> LP map comes from the configured partitioning backend
    (`cfg.partitioner`, core/partition.py) fed with the *final* initial
    positions, so informed backends see the clustered density. The
    default "random" backend consumes k3 exactly as the pre-registry
    round-robin line did — existing seeds reproduce bit-identically.
    """
    n, G = cfg.n_se, mobility_globals(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    pos = jax.random.uniform(k1, (n, 2), maxval=cfg.area)
    wp = jax.random.uniform(k2, (n, 2), maxval=cfg.area)
    mob = jnp.zeros((n, 2), jnp.float32)
    mob_g = jnp.zeros((G, 4), jnp.float32)
    if cfg.mobility in ("hotspot", "group"):
        kg = jax.random.fold_in(key, 0x6b0a)
        mob_g = jax.random.uniform(kg, (G, 4), maxval=cfg.area)
        anchor = mob_g[jnp.arange(n) % G, :2]
        # remap the uniform k1 draw into a per-blob square of side
        # 2 * group_radius around each SE's anchor
        jitter = (pos / cfg.area - 0.5) * (2.0 * cfg.group_radius)
        if cfg.mobility == "group":
            ko = jax.random.fold_in(key, 0x6b0b)
            mob = (jax.random.uniform(ko, (n, 2)) - 0.5) * \
                (2.0 * cfg.group_radius)
            anchor = anchor + mob
            jitter = jitter * 0.1  # members start tight on their slot
        pos = (anchor + jitter) % cfg.area
    elif cfg.mobility == "flock":
        kh = jax.random.fold_in(key, 0x6b0c)
        theta = jax.random.uniform(kh, (n,), maxval=2.0 * jnp.pi)
        mob = jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=1)
    elif cfg.mobility == "trace":
        # k1/k2 are drawn (and discarded) above so the split pattern
        # stays uniform across models; frame 0 is the initial layout
        pos = jnp.asarray(trace_frames(cfg).frames[0])
    lp = part.partition(k3, pos, jnp.ones((n,), jnp.float32),
                        part.from_abm(cfg))
    epi = epidemic_init(key, pos, cfg) if cfg.workload == "epidemic" \
        else jnp.zeros((n,), jnp.int32)
    return {"pos": pos, "waypoint": wp, "lp": lp,
            "mob": mob.astype(jnp.float32), "mob_g": mob_g, "epi": epi}


def toroidal_delta(a, b, area):
    """Shortest per-axis displacement on the torus."""
    d = jnp.abs(a - b)
    return jnp.minimum(d, area - d)


def toroidal_signed_delta(frm, to, area):
    """Signed shortest per-axis displacement frm -> to on the torus."""
    return (to - frm + area / 2.0) % area - area / 2.0


def _unit(v, eps=1e-9):
    """Row-wise unit vector (zero rows stay zero)."""
    norm = jnp.linalg.norm(v, axis=-1, keepdims=True)
    return v / jnp.maximum(norm, eps)


def rwp_draws(key, n: int, cfg: ABMConfig):
    """The fresh-waypoint draw for all n SEs, indexed by global SE id.

    Factored out of `rwp_step` so the sharded engine can compute the
    *same* (n, 2) array on every device and gather each shard's rows by
    SE id — the draw for SE i must be identical no matter which device
    currently hosts it (bit-identity with the single-device oracle)."""
    return jax.random.uniform(key, (n, 2), maxval=cfg.area)


def rwp_apply(pos, waypoint, new_wp, cfg: ABMConfig, speed=None):
    """The deterministic half of a Random-Waypoint move: advance `speed`
    toward the waypoint (torus-aware); on arrival switch to the
    pre-drawn fresh waypoint `new_wp` (sleep time 0). `speed` overrides
    cfg.speed (attractor/leader rows move slower than their SEs)."""
    speed = cfg.speed if speed is None else speed
    delta = waypoint - pos
    # shortest direction on the torus
    delta = jnp.where(delta > cfg.area / 2, delta - cfg.area, delta)
    delta = jnp.where(delta < -cfg.area / 2, delta + cfg.area, delta)
    dist = jnp.linalg.norm(delta, axis=-1, keepdims=True)
    arrived = dist[:, 0] <= speed
    step = jnp.where(dist > 0, delta / jnp.maximum(dist, 1e-9), 0.0)
    new_pos = jnp.where(arrived[:, None], waypoint,
                        (pos + step * speed) % cfg.area)
    next_wp = jnp.where(arrived[:, None], new_wp, waypoint)
    return new_pos % cfg.area, next_wp


def rwp_step(key, pos, waypoint, cfg: ABMConfig):
    """One Random-Waypoint move (draw + apply; see rwp_draws/rwp_apply)."""
    return rwp_apply(pos, waypoint, rwp_draws(key, pos.shape[0], cfg), cfg)


def _globals_step(key, mob_g, cfg: ABMConfig):
    """Advance attractor/leader rows by RWP at a fraction of SE speed.
    Pure in (key, mob_g): every device computes the identical update."""
    g = mob_g.shape[0]
    draw = jax.random.uniform(key, (g, 2), maxval=cfg.area)
    gpos, gwp = rwp_apply(mob_g[:, :2], mob_g[:, 2:], draw, cfg,
                          speed=cfg.speed * _GLOBAL_SPEED_FACTOR)
    return jnp.concatenate([gpos, gwp], axis=1)


def _hotspot_apply(pos, anchor, noise, cfg: ABMConfig):
    """Row-local half of the hotspot move: pull toward the SE's
    attractor, saturating at `speed` beyond the dwell radius; uniform
    noise keeps the blob from collapsing. The stationary blob radius is
    ~0.4 * group_radius. Elementwise per row, so the sharded engine can
    run it on any row subset (anchor/noise gathered by SE id) and still
    match the oracle bit-for-bit."""
    delta = toroidal_signed_delta(pos, anchor, cfg.area)
    dist = jnp.linalg.norm(delta, axis=-1, keepdims=True)
    pull = _unit(delta) * cfg.speed * jnp.minimum(
        1.0, dist / jnp.float32(cfg.group_radius))
    return (pos + pull + noise) % cfg.area


def _group_apply(pos, target, noise, cfg: ABMConfig):
    """Row-local half of the RPGM-lite move: chase (leader + fixed
    member offset) at up to `speed`, with small jitter. Groups migrate
    coherently behind their leader."""
    delta = toroidal_signed_delta(pos, target, cfg.area)
    dist = jnp.linalg.norm(delta, axis=-1, keepdims=True)
    step = _unit(delta) * jnp.minimum(dist, cfg.speed)
    return (pos + step + noise) % cfg.area


def row_local_mobility(cfg: ABMConfig) -> bool:
    """True iff the model factors into (full-size id-order draws) x
    (elementwise per-row apply) — rwp/hotspot/group. The sharded engine
    then moves each shard's rows without any position gather; "flock"
    reads global cell aggregates (a float scatter-add whose reduction
    order must match the oracle), so it stays gather-reconstruct."""
    return cfg.mobility in ("rwp", "hotspot", "group", "trace")


def mobility_row_draws(key, n: int, mob_g, cfg: ABMConfig):
    """Full-size (n, 2) id-order draw arrays for the row-local models,
    plus the advanced global rows. Pure in (key, mob_g): every device
    computes the identical arrays and gathers its own shard's rows by SE
    id, so the draw an SE sees is independent of which device hosts it
    (the bit-identity requirement — same contract as `rwp_draws`).

    Returns (draws, mob_g): draws is {"wp"} for rwp, {"anchor",
    "noise"} for hotspot/group (anchor = the SE's attractor position /
    its group leader's position, noise = the per-step jitter), {"tp"}
    for trace (the next frame, PRNG-free — the frame counter rides
    mob_g[0, 0], a float32 exact for any practical horizon)."""
    if cfg.mobility == "rwp":
        return {"wp": rwp_draws(key, n, cfg)}, mob_g
    if cfg.mobility == "trace":
        frames = jnp.asarray(trace_frames(cfg).frames)
        T = frames.shape[0]
        nxt = mob_g[0, 0].astype(jnp.int32) + 1
        if cfg.trace_policy == "loop":
            idx = nxt % T
        else:  # "hold"; "exact" windows are pre-checked host-side
            idx = jnp.minimum(nxt, T - 1)
        return {"tp": frames[idx]}, mob_g.at[0, 0].add(1.0)
    k_glob = jax.random.fold_in(key, 1)
    k_noise = jax.random.fold_in(key, 2)
    mob_g = _globals_step(k_glob, mob_g, cfg)
    anchor = mob_g[jnp.arange(n) % mob_g.shape[0], :2]
    scale = cfg.speed if cfg.mobility == "hotspot" else 0.5 * cfg.speed
    noise = (jax.random.uniform(k_noise, (n, 2)) - 0.5) * scale
    return {"anchor": anchor, "noise": noise}, mob_g


def mobility_row_apply(pos, waypoint, mob, draws, cfg: ABMConfig):
    """Elementwise per-row half of the row-local models: advance any row
    subset given its rows of the `mobility_row_draws` arrays. Returns
    (pos, waypoint) — `mob` is read-only here (the group member
    offset)."""
    if cfg.mobility == "rwp":
        return rwp_apply(pos, waypoint, draws["wp"], cfg)
    if cfg.mobility == "trace":
        return draws["tp"], waypoint  # replay is the whole move
    if cfg.mobility == "hotspot":
        return _hotspot_apply(pos, draws["anchor"], draws["noise"],
                              cfg), waypoint
    target = (draws["anchor"] + mob) % cfg.area  # group
    return _group_apply(pos, target, draws["noise"], cfg), waypoint


def max_step_displacement(cfg: ABMConfig) -> float:
    """Upper bound on any SE's per-axis displacement in one mobility
    step — the halo-need dilation radius derives from it (see
    parallel/lp_shard.py). rwp/flock move exactly `speed` along a unit
    direction; hotspot adds up to 0.5*speed of per-axis noise on top of
    a speed-capped pull, group up to 0.25*speed on a speed-capped
    chase; trace measures its exact frame-to-frame bound (the `loop`
    policy additionally pays for the trace's wrap-seam jump)."""
    if cfg.mobility == "trace":
        return trace_frames(cfg).max_step_displacement(
            include_seam=cfg.trace_policy == "loop")
    return {"rwp": cfg.speed, "hotspot": 1.5 * cfg.speed,
            "group": 1.25 * cfg.speed, "flock": cfg.speed}[cfg.mobility]


def _flock_step(k_noise, pos, mob, cfg: ABMConfig, valid=None):
    """Flocking-lite over the cell-list grid: steer by inertia +
    alignment with the 3x3-neighborhood mean heading + cohesion toward
    its centroid + noise; move at constant `speed` along the heading.
    Degenerate worlds (no grid) flock against the global mean. `valid`
    (open-world engine) keeps departed rows out of the flock's cell
    aggregates — a dead row must influence nobody."""
    n = pos.shape[0]
    spec = cfg.grid_spec()
    if spec is not None:
        (cdelta, hmean) = neighbors.cell_block_mean(pos, mob, spec,
                                                    cfg.area, valid=valid)
    else:  # un-tessellatable world: one global "cell" (non-toroidal mean)
        if valid is not None:
            vpos = jnp.where(valid[:, None], pos, 0.0)
            vmob = jnp.where(valid[:, None], mob, 0.0)
            csum = vpos.sum(0) - vpos
            hsum = vmob.sum(0) - vmob
            cnt = jnp.maximum(valid.sum() - 1, 1)
        else:
            csum = pos.sum(0) - pos
            hsum = mob.sum(0) - mob
            cnt = jnp.maximum(n - 1, 1)
        cdelta = csum / cnt - pos
        hmean = hsum / cnt
    cohere = _unit(cdelta) * jnp.minimum(
        1.0, jnp.linalg.norm(cdelta, axis=-1, keepdims=True)
        / jnp.float32(cfg.interaction_range))
    noise = (jax.random.uniform(k_noise, (n, 2)) - 0.5) * 2.0
    heading = _unit(mob + 0.8 * _unit(hmean) + 0.6 * cohere + 0.4 * noise)
    # a fully cancelled steer (zero vector) keeps the old heading
    heading = jnp.where(jnp.linalg.norm(heading, axis=-1,
                                        keepdims=True) > 0.5, heading, mob)
    return (pos + heading * cfg.speed) % cfg.area, heading


def mobility_step(key, pos, waypoint, mob, mob_g, cfg: ABMConfig,
                  valid=None):
    """One mobility timestep for all N SEs, in global-SE-id order.

    Returns (pos, waypoint, mob, mob_g). Pure in (key, state): the
    sharded engine reconstructs id-order state, calls this very
    function, and scatters rows back to its slots, so trajectories are
    bit-identical to the single-device oracle by construction (see
    parallel/lp_shard.py). Fields a model does not use pass through
    untouched. `valid` (open-world engine) masks departed rows out of
    any *global* aggregate a model reads (flock's cell means); the
    row-local models ignore it — the caller discards dead rows' moves.
    """
    if row_local_mobility(cfg):
        draws, mob_g = mobility_row_draws(key, pos.shape[0], mob_g, cfg)
        pos, waypoint = mobility_row_apply(pos, waypoint, mob, draws, cfg)
        return pos, waypoint, mob, mob_g
    k_noise = jax.random.fold_in(key, 2)  # flock
    pos, mob = _flock_step(k_noise, pos, mob, cfg, valid=valid)
    return pos, waypoint, mob, mob_g


def _dense_counts(pos, lp, sender_mask, cfg: ABMConfig):
    return neighbors.dense_lp_counts(pos, lp, sender_mask, cfg.n_lp,
                                     cfg.area, cfg.interaction_range)


def interaction_counts_overflow(pos, lp, sender_mask, cfg: ABMConfig,
                                valid=None):
    """Per-sender histogram of recipient LPs, plus the grid's overflow
    alarm.

    Returns (counts, overflow): counts (N, n_lp) int32 with
    counts[i, l] = number of SEs within `interaction_range` of sender i
    currently allocated on LP l (self excluded; non-sender rows zero),
    and overflow () bool — True iff a grid cell exceeded its capacity
    this call, which silently undercounts neighbors (the non-uniform
    mobility models are exactly the workloads that can trip it; the
    engine surfaces it as the per-step `grid_overflow` metric). The
    default grid backend reads the flag off the grid build it performs
    anyway; dense backends are always exact (False).

    `valid` (open-world engine) masks departed rows out of the grid
    build entirely: a dead row with lp = -1 already contributes to no
    LP column (and must not be a sender — the caller folds `valid` into
    `sender_mask`), but keeping it out of the cells also stops stale
    positions from occupying capacity slots or tripping `overflow`. The
    Pallas backends table every row, so they stay closed-world only
    (EngineConfig validation rejects the combination).

    Dispatches on `cfg.proximity_backend`; every backend is bit-identical
    (dense is the oracle — see tests/test_neighbors.py and DESIGN.md
    §Adaptations for the trade-offs).
    """
    backend = cfg.resolved_backend()
    spec = cfg.grid_spec() if backend in ("grid", "pallas_grid") else None
    if backend in ("grid", "pallas_grid") and spec is None:
        backend = "dense"  # world too small to tessellate: exact fallback
    if backend == "grid":
        # every row is swept: the gather-free cell-slab sweep (the row
        # walk, neighbors.rows_grid_counts, serves row subsets)
        return neighbors.slab_lp_counts(
            pos, lp, sender_mask, cfg.n_lp, cfg.area, cfg.interaction_range,
            spec, valid, neighbors.chunk_entries(cfg.mem_budget_mb))
    if backend == "pallas":
        from repro.kernels.proximity.ops import proximity_lp_counts
        return proximity_lp_counts(pos, lp, sender_mask, cfg.n_lp,
                                   cfg.area, cfg.interaction_range), \
            jnp.bool_(False)
    if backend == "pallas_grid":
        from repro.kernels.proximity.ops import proximity_lp_counts_grid
        # the kernel builds its own table; one O(N) bincount yields the
        # same occupancy flag the grid build would have reported
        occ = jnp.zeros((spec.ncell * spec.ncell,), jnp.int32).at[
            neighbors.cell_ids(pos, spec)].add(1)
        return proximity_lp_counts_grid(pos, lp, sender_mask, cfg.n_lp,
                                        cfg.area, cfg.interaction_range,
                                        spec), occ.max() > spec.capacity
    return _dense_counts(pos, lp, sender_mask, cfg), jnp.bool_(False)


def interaction_counts(pos, lp, sender_mask, cfg: ABMConfig):
    """`interaction_counts_overflow` without the alarm (same contract)."""
    return interaction_counts_overflow(pos, lp, sender_mask, cfg)[0]


def walk_slots(cfg: ABMConfig) -> int:
    """Candidate slots one call of `interaction_counts_overflow` tests,
    senders or not: the cell-slab sweep's cell rows x (ncell + 2) x 9 x
    capacity^2, padded to whole chunks (`neighbors.slab_walk`; one
    receiver layer, a layer more per `capacity` ranks of overflow),
    the Pallas grid kernel's N x 9 x capacity, N^2 for the dense sweeps.
    The useful share of the walk is the in-range sender pairs over
    this."""
    backend = cfg.resolved_backend()
    spec = cfg.grid_spec() if backend in ("grid", "pallas_grid") else None
    n = cfg.n_se
    if spec is None:
        return n * n
    if backend == "grid":
        return neighbors.slab_walk(
            spec.ncell, spec.capacity,
            neighbors.chunk_entries(cfg.mem_budget_mb))[2]
    return n * 9 * spec.capacity


# ---------------------------------------------------------------------------
# Epidemic/gossip diffusion workload (ABMConfig.workload == "epidemic")
# ---------------------------------------------------------------------------
# State is one int32 flag per SE (`epi`: 0 susceptible, 1 infectious)
# that travels with the row through migrations and resharding. The
# update factors exactly like the row-local mobility models do —
# full-size id-order draw arrays x an elementwise per-row transition —
# so the sharded engine gathers each shard's draw rows by SE id and
# stays bit-identical to the oracle wherever a row is hosted.


def epidemic_init(key, pos, cfg: ABMConfig):
    """Initial infection flags: the k = max(1, round(epi_seed_frac*n))
    SEs nearest (torus metric) to one key-drawn origin start
    infectious — a spatial patch, not a uniform sprinkle, so the wave
    has somewhere to travel *from* and load genuinely shifts across
    LPs as it spreads. Deterministic in (key, pos): every device
    computes the identical flags."""
    n = pos.shape[0]
    k = max(1, int(round(cfg.epi_seed_frac * n)))
    origin = jax.random.uniform(jax.random.fold_in(key, EPI_SEED_SALT),
                                (2,), maxval=cfg.area)
    d = toroidal_delta(pos, origin[None, :], cfg.area)
    d2 = d[:, 0] ** 2 + d[:, 1] ** 2
    thresh = jnp.sort(d2)[k - 1]
    return (d2 <= thresh).astype(jnp.int32)


def epidemic_send_prob(epi, cfg: ABMConfig):
    """Per-SE interaction probability: infectious SEs send
    `epi_boost`x more often (capped at 1). This is the load-shift
    mechanism — event weight follows the infection wave, not the
    density map, which is what stresses self-clustering beyond any
    pure-mobility scenario."""
    p = jnp.float32(cfg.p_interact)
    hot = jnp.minimum(p * jnp.float32(cfg.epi_boost), jnp.float32(1.0))
    return jnp.where(epi > 0, hot, p)


def epidemic_draws(key, n: int, cfg: ABMConfig):
    """Full-size (n,) id-order uniforms for the infection (and, when
    epi_gamma > 0, recovery) trials — same device-independence
    contract as `mobility_row_draws`. Streams are salted off the step
    key, so no existing draw moves."""
    d = {"u_inf": jax.random.uniform(
        jax.random.fold_in(key, EPI_INFECT_SALT), (n,))}
    if cfg.epi_gamma > 0.0:
        d["u_rec"] = jax.random.uniform(
            jax.random.fold_in(key, EPI_RECOVER_SALT), (n,))
    return d


def epidemic_row_update(epi, exposure, draws, cfg: ABMConfig):
    """Elementwise SI/SIS transition for any row subset: a susceptible
    row with `exposure` in-range infectious senders catches with
    p = 1 - (1-beta)^exposure (independent per-contact trials); with
    SIS (epi_gamma > 0) an infectious row recovers to susceptible with
    gamma. Zero exposure gives p = 0, so dead/padded rows (exposure 0
    by construction) never transition."""
    p_inf = 1.0 - jnp.power(jnp.float32(1.0 - cfg.epi_beta),
                            exposure.astype(jnp.float32))
    catch = (epi == 0) & (draws["u_inf"] < p_inf)
    out = jnp.where(catch, 1, epi)
    if cfg.epi_gamma > 0.0:
        rec = (epi > 0) & (draws["u_rec"] < jnp.float32(cfg.epi_gamma))
        out = jnp.where(rec, 0, out)
    return out


def epidemic_exposure_overflow(pos, labels, query_mask, cfg: ABMConfig,
                               valid=None):
    """exposure[i] = #{j != i in interaction_range with labels[j] == 1}
    for rows with `query_mask` (zeros elsewhere), plus the grid
    overflow alarm. `labels` carries 1 on the infectious rows that
    actually sent this step, 0 on other live rows, and -1 on dead rows
    (one_hot drops them from the dense path; `valid` keeps them out of
    the grid build).

    This is the proximity phase's cell-slab sweep with a 2-class label
    array instead of the LP map — grid and dense stay bit-identical by
    the same argument, and the one extra sweep is the entire cost of
    the workload."""
    backend = cfg.resolved_backend()
    spec = cfg.grid_spec() if backend == "grid" else None
    if spec is not None:
        counts, overflow = neighbors.slab_lp_counts(
            pos, labels, query_mask, 2, cfg.area, cfg.interaction_range,
            spec, valid, neighbors.chunk_entries(cfg.mem_budget_mb))
        return counts[:, 1], overflow
    counts = neighbors.dense_lp_counts(pos, labels, query_mask, 2,
                                       cfg.area, cfg.interaction_range)
    return counts[:, 1], jnp.bool_(False)
