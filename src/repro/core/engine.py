"""The GAIA adaptive-partitioning engine (paper §4), vectorized in JAX.

One `lax.scan` step = one simulation timestep:

  1. apply migrations whose protocol delay has elapsed (the SE becomes
     active on the destination LP — paper Fig. 4: decision at t,
     notifications at t/t+1, migration message in flight, active at t+2;
     with symmetric load balancing two more negotiation steps precede it)
  2. move agents (RWP), draw senders, deliver proximity interactions
  3. account local vs remote deliveries (LCR numerator/denominator)
  4. update the heuristic window; evaluate candidates
  5. constrain candidates through the load balancer; admitted SEs enter
     the in-flight state

Correctness invariant (tested): the model evolution (positions,
interaction sets) is identical with GAIA ON and OFF — the partitioning
layer only changes WHERE events are delivered, never WHAT happens, which
is the paper's transparency requirement (§4.2).

Execution layers (EngineConfig.sharding): "none" runs every LP inside
one device's scan (this module); "lp_device" maps LPs onto a JAX device
mesh where each device owns its LPs' SE rows and GAIA migrations
physically reshard state (parallel/lp_shard.py) — bit-identical to
"none" on the same seed (tests/test_sharding.py).

A third transparent layer batches replicas: `run_batch(cfg, seeds)`
vmaps the memoized jitted scan over a leading seed axis, with per-seed
bit-identity to sequential runs on both execution layers
(tests/test_replicas.py) — the substrate of every mean/std/ci95/n
number the benchmarks report (core/stats.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import balance as bal
from repro.core import partition as part
from repro.core import abm as _abm
from repro.core.abm import (ABMConfig, check_trace_horizon,
                            epidemic_draws, epidemic_exposure_overflow,
                            epidemic_row_update, epidemic_send_prob,
                            init_abm, interaction_counts_overflow,
                            mobility_step)
from repro.core.costmodel import ExecutionEnvironment
from repro.core.heuristics import HeuristicConfig
from repro.core import heuristics as heu
from repro.obs.config import ObsConfig
from repro.obs import ledger as obs_ledger
from repro.obs import runtime as obs_runtime


SHARDINGS = ("none", "lp_device")

#: PRNG salt for the periodic-repartition stream: folded into the
#: per-step k_move, so the default path (repartition_every=0) consumes
#: the main key stream exactly as before (bit-identical seeds)
REPART_SALT = 0x7a47


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    abm: ABMConfig = ABMConfig()
    heuristic: HeuristicConfig = HeuristicConfig()
    gaia_on: bool = True
    balance: str = "symmetric"  # "symmetric" | "asymmetric"
    migration_delay: int = 5  # 2 (LB negotiation) + 3 (protocol, Fig. 4)
    timesteps: int = 1200
    capacity: Optional[tuple] = None  # asymmetric LP capacity shares
    # execution environment (costmodel.ExecutionEnvironment): prices the
    # run's flows offline (wct_env) and, when `capacity` is unset,
    # supplies the asymmetric balancer's capacity profile (per-LP
    # relative speed, paper §4.4)
    env: Optional[ExecutionEnvironment] = None
    # --- sharded execution (parallel/lp_shard.py) -----------------------
    # "none": every LP inside one device's scan (the oracle).
    # "lp_device": LPs mapped onto a device mesh; each device owns its
    # LPs' SE rows, GAIA migrations physically reshard. Bit-identical
    # to "none" on the same seed (tests/test_sharding.py).
    sharding: str = "none"
    n_devices: int = 0  # 0 = all visible devices (capped at n_lp)
    shard_capacity: int = 0  # SE slots per device; 0 = auto (2x share)
    mig_capacity: int = 0  # migration-buffer rows/device/step; 0 = auto
    # halo-exchange buffer rows per (src, dst) device pair per step; the
    # exchange is exact as long as no device needs more than this many
    # rows from any single peer (overflow raises the shard_overflow
    # alarm, like the other capacities). 0 = auto (= shard capacity,
    # safe for arbitrary partitions); tighten once GAIA has clustered
    # the shards to shrink the static all_to_all transport.
    halo_capacity: int = 0
    # --- periodic global repartition (core/partition.py) ----------------
    # every R steps the abm.partitioner backend recomputes the SE -> LP
    # map from current geometry; the delta rides the normal migration
    # machinery (pending_dst/pending_eta, full-row resharding under
    # "lp_device") and is counted in migrations/mig_flows, so the state
    # transfer is priced by wct/wct_env exactly like GAIA migrations.
    # 0 = never (the default path is bit-identical to pre-registry runs).
    repartition_every: int = 0
    # hard memory budget (MiB) for the scale tier: propagated into
    # abm.mem_budget_mb (CSR chunk transients + grid-capacity clamp) and
    # into the sharded layout's halo/migration slot buffers
    # (lp_shard.make_shard_spec). 0 = unbudgeted historical defaults; an
    # explicit abm.mem_budget_mb wins over the engine-level knob.
    mem_budget_mb: int = 0
    # --- open-world churn (core/service.py) -----------------------------
    # open_world=True turns the fixed-N state into a slot universe of
    # abm.n_se rows with a live/dead mask (lp >= 0 marks live; the
    # sharded layer reuses its gid >= 0 mask): SEs arrive into and
    # depart from free slots mid-run (Engine.arrive/.depart), every step
    # phase masks dead rows, and with zero churn + a full population the
    # trajectory stays bit-identical to the closed-world engine.
    # n_active caps the initial live population (0 = all n_se live);
    # abm.n_se - n_active slots start free for arrivals.
    open_world: bool = False
    n_active: int = 0
    # --- runtime telemetry (repro.obs) ----------------------------------
    # obs.enabled=True threads the per-step metrics ledger through the
    # compiled scan (ring buffer + async drain every obs.drain_every
    # steps) and lets the service layer synthesize events. Disabled, the
    # compiled program is byte-identical to a config without the field
    # (window_key_cfg normalizes a disabled ObsConfig away); enabled, it
    # legitimately changes the traced scan and so splits the cache.
    # Either way results are bit-identical (tests/test_obs.py).
    obs: ObsConfig = ObsConfig()

    def __post_init__(self):
        if self.mem_budget_mb > 0 and self.abm.mem_budget_mb == 0:
            object.__setattr__(self, "abm", dataclasses.replace(
                self.abm, mem_budget_mb=self.mem_budget_mb))
        if self.sharding not in SHARDINGS:
            raise ValueError(
                f"sharding={self.sharding!r} not in {SHARDINGS}")
        if self.balance not in ("symmetric", "asymmetric"):
            raise ValueError(
                f"balance={self.balance!r} not in ('symmetric', "
                "'asymmetric')")
        if self.timesteps < 0 or self.migration_delay < 1:
            raise ValueError("timesteps must be >= 0 and migration_delay "
                             ">= 1")
        if min(self.n_devices, self.shard_capacity, self.mig_capacity,
               self.halo_capacity, self.mem_budget_mb) < 0:
            raise ValueError("n_devices and the shard/mig/halo/memory "
                             "capacities must be >= 0 (0 = auto)")
        if self.repartition_every < 0:
            raise ValueError("repartition_every must be >= 0")
        if self.halo_capacity > 0 and self.mem_budget_mb > 0 and \
                self.halo_capacity * 48 > (self.mem_budget_mb << 18):
            # explicit halo_capacity wins over the budget-derived auto
            # size (lp_shard.make_shard_spec), so the two knobs can
            # contradict: reject a capacity whose per-pair send+recv
            # buffers (2 peers x 2 buffers x 12 B/row) already exceed
            # the quarter-budget the halo is allotted
            raise ValueError(
                f"halo_capacity={self.halo_capacity} needs more than "
                f"mem_budget_mb={self.mem_budget_mb} affords the halo "
                "buffers; raise the budget or drop one of the knobs")
        if self.env is not None and self.env.n_lp != self.abm.n_lp:
            raise ValueError(
                f"env {self.env.name!r} has {self.env.n_lp} LPs but "
                f"abm.n_lp={self.abm.n_lp}")
        if self.balance == "asymmetric" and self.effective_capacity() is None:
            raise ValueError("asymmetric balance needs `capacity` or an "
                             "`env` to derive it from")
        if not 0 <= self.n_active <= self.abm.n_se:
            raise ValueError(
                f"n_active={self.n_active} must be in [0, n_se="
                f"{self.abm.n_se}] (0 = all live)")
        if self.n_active > 0 and not self.open_world:
            raise ValueError("n_active needs open_world=True")
        if self.open_world and \
                self.abm.proximity_backend.startswith("pallas"):
            raise ValueError(
                "open_world=True needs proximity_backend 'grid' or "
                "'dense' (the Pallas kernels table every row and have "
                "no dead-slot mask)")

    def effective_capacity(self) -> Optional[tuple]:
        """Asymmetric capacity shares: explicit `capacity` wins, else the
        environment's relative LP speeds (normalized), else None."""
        if self.capacity is not None:
            return tuple(self.capacity)
        if self.env is not None:
            return self.env.capacity_shares()
        return None

    def initial_live(self) -> int:
        """Live SEs at t=0: `n_active` under open_world (0 = full), the
        whole population otherwise."""
        if self.open_world and self.n_active > 0:
            return self.n_active
        return self.abm.n_se


def _init_engine(key, cfg: EngineConfig):
    if cfg.sharding == "lp_device":
        from repro.parallel import lp_shard
        return lp_shard.init_sharded(key, cfg, lp_shard.make_shard_spec(cfg))
    k1, k2 = jax.random.split(key)
    st = init_abm(k1, cfg.abm)
    n, L = cfg.abm.n_se, cfg.abm.n_lp
    st.update(heu.init_state(cfg.heuristic, n, L))
    st.update({
        "key": k2,
        "t": jnp.int32(0),
        "pending_dst": jnp.full((n,), -1, jnp.int32),
        "pending_eta": jnp.full((n,), -1, jnp.int32),
    })
    live = cfg.initial_live()
    if cfg.open_world and live < n:
        # slots [live, n) start free: lp < 0 is THE oracle dead mask
        # (mirroring the sharded layer's gid < 0). The PRNG consumption
        # above is unchanged, so the live prefix is bit-identical to
        # the closed-world rows 0..live-1 of the same seed.
        dead = jnp.arange(n) >= live
        st["lp"] = jnp.where(dead, -1, st["lp"])
    return st


def step_phases(cfg: EngineConfig):
    """Ordered (name, fn) phase decomposition of one oracle timestep.

    Each phase is a pure function over a growing "phase context" dict
    `px` (state under "st", plus the intermediates earlier phases
    added). `step` composes the phases fused — same ops, same order, so
    the compiled scan is the historical program — each under a
    `step.<phase>` named scope, which the compiled program keeps as the
    `op_name` of its ops: a profiler trace attributes device time to
    phases by it. Inactive phases (repartition with
    repartition_every=0, heuristic with gaia_on=False) are simply
    absent from the list."""
    n, L = cfg.abm.n_se, cfg.abm.n_lp
    ow = cfg.open_world

    def ph_migrate(px):
        # 1. complete in-flight migrations
        st = px["st"]
        t = st["t"]
        key, k_move, k_send = jax.random.split(st["key"], 3)
        arrive = st["pending_eta"] == t
        lp = jnp.where(arrive, st["pending_dst"], st["lp"])
        pending_dst = jnp.where(arrive, -1, st["pending_dst"])
        pending_eta = jnp.where(arrive, -1, st["pending_eta"])
        valid = (lp >= 0) if ow else None
        return dict(px, t=t, key=key, k_move=k_move, k_send=k_send, lp=lp,
                    pending_dst=pending_dst, pending_eta=pending_eta,
                    valid=valid)

    def ph_mobility(px):
        # 2. model evolution (identical regardless of partitioning)
        st, valid = px["st"], px["valid"]
        pos, wp, mob, mob_g = mobility_step(
            px["k_move"], st["pos"], st["waypoint"], st["mob"],
            st["mob_g"], cfg.abm, valid=valid)
        if ow:  # dead rows hold their slot state (pure selection: no
            # bits of any live row change when every row is live)
            pos = jnp.where(valid[:, None], pos, st["pos"])
            wp = jnp.where(valid[:, None], wp, st["waypoint"])
            mob = jnp.where(valid[:, None], mob, st["mob"])
        if cfg.abm.workload == "epidemic":
            # infectious SEs (last step's flags — this step's infections
            # are decided by ph_workload *from* these senders) interact
            # epi_boost x more often: the draw becomes an explicit
            # uniform against a per-SE probability. Static branch: the
            # non-epidemic path keeps the exact historical bernoulli.
            sender = jax.random.uniform(px["k_send"], (n,)) \
                < epidemic_send_prob(st["epi"], cfg.abm)
        else:
            sender = jax.random.bernoulli(px["k_send"],
                                          cfg.abm.p_interact, (n,))
        if ow:
            sender = valid & sender
        return dict(px, pos=pos, wp=wp, mob=mob, mob_g=mob_g, sender=sender)

    def ph_proximity(px):
        counts, grid_ovf = interaction_counts_overflow(
            px["pos"], px["lp"], px["sender"], cfg.abm,
            valid=px["valid"])  # (N, L), () bool
        return dict(px, counts=counts, grid_ovf=grid_ovf)

    def ph_workload(px):
        # epidemic diffusion over the proximity graph: susceptible SEs
        # count the in-range infectious rows that sent this step (one
        # more candidate walk with a 2-class label array) and run the
        # SI/SIS transition on full-size id-order draws — the same
        # draws x elementwise-apply factoring as row-local mobility,
        # so the sharded mirror is bit-identical by construction
        st, valid = px["st"], px["valid"]
        epi = st["epi"]
        eis = (epi > 0) & px["sender"]
        labels = eis.astype(jnp.int32)
        if ow:  # dead rows drop out of the label sweep entirely
            labels = jnp.where(valid, labels, -1)
        qmask = (epi == 0) & valid if ow else (epi == 0)
        exposure, ovf = epidemic_exposure_overflow(
            px["pos"], labels, qmask, cfg.abm, valid=valid)
        draws = epidemic_draws(px["k_move"], n, cfg.abm)
        epi = epidemic_row_update(epi, exposure, draws, cfg.abm)
        infected = ((epi > 0) & valid if ow else (epi > 0)).sum()
        return dict(px, epi=epi, infected=infected,
                    grid_ovf=px["grid_ovf"] | ovf)

    def ph_account(px):
        # 3. communication accounting: the per-pair flow matrix (src LP
        # -> dst LP; integer scatter-add, so sharded psum reproduces it
        # exactly) is the single source of truth — the scalar LCR terms
        # are its trace and total. Dead rows' counts are all-zero, so
        # clipping their lp = -1 to row 0 adds nothing.
        lp = px["lp"]
        safe_lp = jnp.clip(lp, 0, L - 1) if ow else lp
        flows = jnp.zeros((L, L), jnp.int32).at[safe_lp].add(px["counts"])
        local = jnp.trace(flows)
        total = flows.sum()
        st = px["st"]
        hstate = {k: st[k] for k in ("ring", "ptr", "since_eval",
                                     "last_mig")}
        return dict(px, safe_lp=safe_lp, flows=flows, local=local,
                    total=total, remote=total - local, hstate=hstate,
                    migs=jnp.int32(0), n_evals=jnp.int32(0),
                    mig_flows=jnp.zeros((L, L), jnp.int32),
                    reparts=jnp.int32(0))

    def ph_repartition(px):
        # every R steps the configured backend recomputes the global map
        # from current geometry; the delta enters the ordinary in-flight
        # migration machinery (and the migration counters, so wct/wct_env
        # price the state transfer). SEs already in flight are skipped —
        # their pending move completes first.
        lp, valid, pos, t = px["lp"], px["valid"], px["pos"], px["t"]
        pending_dst, pending_eta = px["pending_dst"], px["pending_eta"]
        pcfg = part.from_engine(cfg)
        k_rep = jax.random.fold_in(px["k_move"], REPART_SALT)
        do = (t > 0) & (t % cfg.repartition_every == 0)
        # hysteresis-aware backends (part.uses_prev) see the current map;
        # the others get prev=None so their dispatch is byte-identical
        # to the historical call (and so the sharded mirror only pays
        # the id-order LP gather when the backend actually reads it)
        prev = lp if part.uses_prev(pcfg) else None
        # open world: dead rows get zero weight AND zero position, so
        # the partitioner sees byte-identical inputs on both execution
        # layers (the sharded mirror reconstructs dead ids as zeros)
        weights = (valid.astype(jnp.float32) if ow
                   else jnp.ones((n,), jnp.float32))
        ppos = jnp.where(valid[:, None], pos, 0.0) if ow else pos
        new_lp = jax.lax.cond(
            do,
            lambda: part.partition(k_rep, ppos, weights, pcfg, prev=prev),
            lambda: lp)
        move = (new_lp != lp) & (pending_dst < 0)
        if ow:  # free slots never enter the migration machinery
            move = move & valid
        pending_dst = jnp.where(move, new_lp, pending_dst)
        pending_eta = jnp.where(move, t + cfg.migration_delay, pending_eta)
        hstate = dict(px["hstate"],
                      last_mig=jnp.where(move, t, px["hstate"]["last_mig"]))
        reparts = move.sum()
        mig_flows = px["mig_flows"].at[px["safe_lp"], new_lp].add(
            move.astype(jnp.int32))
        return dict(px, pending_dst=pending_dst, pending_eta=pending_eta,
                    hstate=hstate, reparts=reparts,
                    migs=px["migs"] + reparts, mig_flows=mig_flows)

    def ph_heuristic(px):
        # 4/5. self-clustering: window update, evaluation, balancing
        lp, valid, t, safe_lp = px["lp"], px["valid"], px["t"], px["safe_lp"]
        pending_dst, pending_eta = px["pending_dst"], px["pending_eta"]
        hstate = heu.update_window(cfg.heuristic, px["hstate"],
                                   px["counts"], px["sender"], t)
        cand, dest, alpha, hstate, n_evals = heu.evaluate(
            cfg.heuristic, hstate, lp, t, valid=valid, mf=px["mf"])
        cand = cand & (pending_dst < 0)  # not already in flight
        cmat = bal.candidate_matrix(cand, safe_lp, dest, L)
        if cfg.balance == "asymmetric":
            cap = jnp.asarray(cfg.effective_capacity(), jnp.float32)
            # lp = -1 buckets into the extra row L, then drops
            current = jnp.bincount(jnp.where(lp < 0, L, lp),
                                   length=L + 1)[:L] if ow else \
                jnp.bincount(lp, length=L)
            grants = bal.asymmetric_grants(cmat, current, cap)
        else:
            grants = bal.symmetric_grants(cmat)
        admit = bal.select_migrations(cand, safe_lp, dest, alpha, grants, L)
        pending_dst = jnp.where(admit, dest, pending_dst)
        pending_eta = jnp.where(admit, t + cfg.migration_delay, pending_eta)
        hstate = dict(hstate, last_mig=jnp.where(admit, t,
                                                 hstate["last_mig"]))
        mig_flows = px["mig_flows"].at[safe_lp, dest].add(
            admit.astype(jnp.int32))
        return dict(px, pending_dst=pending_dst, pending_eta=pending_eta,
                    hstate=hstate, n_evals=n_evals,
                    migs=px["migs"] + admit.sum(), mig_flows=mig_flows)

    def ph_finalize(px):
        new_state = dict(px["st"], key=px["key"], t=px["t"] + 1,
                         pos=px["pos"], waypoint=px["wp"], lp=px["lp"],
                         mob=px["mob"], mob_g=px["mob_g"],
                         pending_dst=px["pending_dst"],
                         pending_eta=px["pending_eta"], **px["hstate"])
        local, total = px["local"], px["total"]
        metrics = {
            "local_msgs": local.astype(jnp.float32),
            "remote_msgs": px["remote"].astype(jnp.float32),
            "migrations": px["migs"].astype(jnp.float32),
            "heu_evals": px["n_evals"].astype(jnp.float32),
            "lcr": local.astype(jnp.float32)
                   / jnp.maximum(total.astype(jnp.float32), 1.0),
            "lp_flows": px["flows"],
            "mig_flows": px["mig_flows"],
            # bulk moves issued by the periodic global repartition (a
            # subset of `migrations`: same machinery and pricing)
            "repartitions": px["reparts"].astype(jnp.float32),
            # exactness alarm: a grid cell over capacity silently
            # undercounts neighbors — the clustered mobility models are
            # what can trip it
            "grid_overflow": px["grid_ovf"].astype(jnp.float32),
        }
        if ow:
            # live population after this step's migration completions —
            # the churn service's occupancy signal (-> mean_pop)
            metrics["pop"] = px["valid"].sum().astype(jnp.float32)
        if cfg.abm.workload == "epidemic":
            new_state["epi"] = px["epi"]
            metrics["infected"] = px["infected"].astype(jnp.float32)
        return dict(px, new_state=new_state, metrics=metrics)

    phases = [("migrate", ph_migrate), ("mobility", ph_mobility),
              ("proximity", ph_proximity), ("accounting", ph_account)]
    if cfg.abm.workload == "epidemic":
        phases.insert(3, ("workload", ph_workload))
    if cfg.repartition_every > 0:
        phases.append(("repartition", ph_repartition))
    if cfg.gaia_on:
        phases.append(("heuristic", ph_heuristic))
    phases.append(("finalize", ph_finalize))
    return phases


def step(state, cfg: EngineConfig, mf=None):
    """One timestep. Returns (state, per-step metrics). `mf` optionally
    overrides cfg.heuristic.mf with a traced value (see run_window).

    Open world (cfg.open_world): rows with lp < 0 are free slots — they
    draw the same per-id randomness (shapes never depend on the
    population, which is what keeps zero-churn runs bit-identical to
    the closed-world path) but are masked out of every effect: they
    never move, never send, never receive (lp = -1 one-hots to no
    column and `valid` keeps them out of the grid), never evaluate, and
    never migrate.

    The body is the fused composition of `step_phases`, each phase
    under its `step.<phase>` named scope (the profiler trace's phase
    attribution; scopes add no ops). Host spans belong to the caller
    (`core/service.Engine.step`), never to this traced body."""
    px = {"st": state, "mf": mf}
    for name, fn in step_phases(cfg):
        with jax.named_scope(f"step.{name}"):
            px = fn(px)
    return px["new_state"], px["metrics"]


# ---------------------------------------------------------------------------
# open-world churn ops (oracle layer; sharded mirrors in parallel/lp_shard)
# ---------------------------------------------------------------------------


def _clear_slot_history(st, tgt):
    """Reset the per-slot protocol + heuristic history at rows `tgt`
    (index n = dropped padding) to their init_state values, so a reused
    slot carries nothing of its previous occupant."""
    st["pending_dst"] = st["pending_dst"].at[tgt].set(-1, mode="drop")
    st["pending_eta"] = st["pending_eta"].at[tgt].set(-1, mode="drop")
    st["ring"] = st["ring"].at[:, tgt, :].set(0, mode="drop")
    st["ptr"] = st["ptr"].at[tgt].set(0, mode="drop")
    st["since_eval"] = st["since_eval"].at[tgt].set(0, mode="drop")
    st["last_mig"] = st["last_mig"].at[tgt].set(-10**6, mode="drop")
    return st


def oracle_arrive(state, ids, rows):
    """Insert a batch of SEs into free slots `ids` (int32; -1 entries
    are padding and write nothing). `rows` supplies per-arrival "pos"
    (B, 2) and "lp" (B,), optionally "waypoint" / "mob" (default: the
    arrival position / zeros). O(B) scatter into device state — the
    free-slot pool and overflow accounting are host-side
    (core/service.py: Engine.arrive)."""
    n = state["lp"].shape[0]
    tgt = jnp.where(ids >= 0, ids, n)
    pos = jnp.asarray(rows["pos"], jnp.float32)
    st = dict(state)
    st["pos"] = st["pos"].at[tgt].set(pos, mode="drop")
    st["waypoint"] = st["waypoint"].at[tgt].set(
        jnp.asarray(rows.get("waypoint", pos), jnp.float32), mode="drop")
    st["mob"] = st["mob"].at[tgt].set(
        jnp.asarray(rows.get("mob", jnp.zeros_like(pos)), jnp.float32),
        mode="drop")
    st["lp"] = st["lp"].at[tgt].set(
        jnp.asarray(rows["lp"], jnp.int32), mode="drop")
    st["epi"] = st["epi"].at[tgt].set(
        jnp.asarray(rows.get("epi", jnp.zeros(pos.shape[:1], jnp.int32)),
                    jnp.int32), mode="drop")
    return _clear_slot_history(st, tgt)


def oracle_depart(state, ids):
    """Remove the SEs in slots `ids` (int32; -1 = padding): lp = -1
    frees the slot, and the slot history resets so the next occupant
    starts clean. O(B) scatter."""
    n = state["lp"].shape[0]
    tgt = jnp.where(ids >= 0, ids, n)
    st = dict(state)
    st["lp"] = st["lp"].at[tgt].set(-1, mode="drop")
    st["epi"] = st["epi"].at[tgt].set(0, mode="drop")
    return _clear_slot_history(st, tgt)


class HostReads:
    """`np.asarray` that counts its calls: the device-to-host transfers
    one counter read makes (the `fetches` of its readback span)."""

    def __init__(self):
        self.count = 0

    def __call__(self, x):
        self.count += 1
        return np.asarray(x)


#: the window summary's scalar counters, in packing order: (counter,
#: series key, reduction over the step axis). A key the series lacks
#: drops out, so one layout adapts to every config: open world's `pop`,
#: the epidemic's `infected`, the sharded layer's `halo_frac` and
#: `shard_overflow`.
SUMMARY_SCALARS = (
    ("local_msgs", "local_msgs", "sum"),
    ("remote_msgs", "remote_msgs", "sum"),
    ("migrations", "migrations", "sum"),
    ("heu_evals", "heu_evals", "sum"),
    ("mean_lcr", "lcr", "mean"),
    ("mean_pop", "pop", "mean"),
    ("mean_infected", "infected", "mean"),
    ("final_infected", "infected", "last"),
    ("grid_overflow", "grid_overflow", "sum"),
    ("repartitions", "repartitions", "sum"),
    ("mean_halo_frac", "halo_frac", "mean"),
    ("shard_overflow", "shard_overflow", "sum"),
)

#: the per-pair int32 flow series: the summary carries them per step and
#: the host sums them in int64, so that long windows cannot wrap int32
SUMMARY_FLOWS = ("lp_flows", "mig_flows", "wire_flows")

_REDUCE = {"sum": lambda x: x.sum(), "mean": lambda x: x.mean(),
           "last": lambda x: x[-1]}


def series_shapes(series) -> dict:
    """The per-step series' shapes: all `unpack` needs of the series."""
    return {k: tuple(v.shape) for k, v in series.items()}


def window_summary(series):
    """Traceable half of the counter read: one window's counters as one
    flat int32 buffer, the scalars first (their f32 bits) and then each
    flow series whole. Each scalar is the same jnp reduction over the
    step axis that an eager read of the series would run."""
    scalars = jnp.stack([_REDUCE[how](series[k])
                         for _, k, how in SUMMARY_SCALARS if k in series])
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(scalars.astype(jnp.float32),
                                      jnp.int32)]
        + [series[k].reshape(-1) for k in SUMMARY_FLOWS if k in series])


def unpack(buf, shapes) -> dict:
    """Host half of the counter read: a fetched `window_summary` buffer
    -> the run-counter dict, given the series' `shapes`. Flow matrices
    sum to nested int64 lists; `wire_flows` adds their total,
    `bytes_on_wire`."""
    buf = np.asarray(buf)
    names = [c for c, k, _ in SUMMARY_SCALARS if k in shapes]
    at = len(names)
    counters = dict(zip(names, buf[:at].view(np.float32).tolist()))
    for k in SUMMARY_FLOWS:
        if k in shapes:
            size = int(np.prod(shapes[k]))
            flows = buf[at:at + size].reshape(shapes[k]).sum(
                axis=0, dtype=np.int64)
            at += size
            counters[k] = flows.tolist()
            if k == "wire_flows":
                counters["bytes_on_wire"] = float(flows.sum())
    return counters


def series_counters(series, read=np.asarray) -> dict:
    """Aggregate a per-step metrics series into run counters, eagerly:
    `unpack(read(window_summary(series)))`, the one counter/series key
    contract, in one transfer. The window programs carry the same
    summary as an output of their own (`_compiled_window`)."""
    return unpack(read(window_summary(series)), series_shapes(series))


def batch_summary(series):
    """`window_summary` of each replica of a batched (T, R, ...) series,
    stacked to (R, K): one buffer for the whole batch."""
    return jnp.stack([window_summary(replica_series(series, r))
                      for r in range(series["lcr"].shape[1])])


def unpack_batch(buf, shapes) -> list:
    """Per-replica counters from a fetched `batch_summary` buffer, given
    the batched series' (T, R, ...) `shapes`."""
    rep = {k: s[:1] + s[2:] for k, s in shapes.items()}
    return [unpack(row, rep) for row in np.asarray(buf)]


def _trace_guard(state, cfg: EngineConfig, n_steps: int) -> None:
    """Window-runner front door of `abm.check_trace_horizon`: reads the
    resident state's step counter (lockstep across replicas and
    replicated across shards, so any element is THE clock) and
    validates the window before anything is traced."""
    if cfg.abm.mobility != "trace" or cfg.abm.trace_policy != "exact":
        return
    t0 = int(np.asarray(jax.device_get(state["t"])).reshape(-1)[0])
    check_trace_horizon(cfg.abm, t0, n_steps)


def window_key_cfg(cfg: EngineConfig) -> EngineConfig:
    """Normalize a config to its compiled-scan cache key: MF is a
    dynamic argument and the scan length comes from n_steps, so neither
    may split the cache. A *disabled* ObsConfig is normalized to the
    default one — whatever drain/threshold knobs it carries are host
    policy that never reaches the traced program, so configs differing
    only there share one executable (this identity is also the
    telemetry-off zero-op proof tests/test_obs.py leans on). An
    *enabled* ObsConfig stays: it legitimately changes the program.
    Shared by the oracle and sharded runners."""
    return dataclasses.replace(
        cfg, timesteps=0,
        heuristic=dataclasses.replace(cfg.heuristic, mf=0.0),
        obs=cfg.obs if cfg.obs.enabled else ObsConfig())


def strip_obs(cfg: EngineConfig) -> EngineConfig:
    """Drop telemetry from a config: the batched replica scans and the
    sharded churn kernels are deliberately un-instrumented (the ledger
    covers the single-replica resident paths — see DESIGN.md
    §Observability), so their compiled-cache keys must not split when a
    resident engine turns telemetry on."""
    if not cfg.obs.enabled:
        return cfg
    return dataclasses.replace(cfg, obs=ObsConfig())


#: bound on each compiled-scan memo (engine window/batch + their sharded
#: mirrors in parallel/lp_shard.py): a benchmark sweep leaks one compiled
#: executable per (cfg shape, n_steps) under the old maxsize=None, which
#: the extended scaling matrix turns from a nuisance into gigabytes —
#: LRU eviction keeps the working set of any one sweep while old shapes
#: age out. Harnesses that iterate many shapes call
#: `clear_compiled_caches()` between cells instead of relying on it.
COMPILED_CACHE_SIZE = 32


def clear_compiled_caches() -> None:
    """Drop every memoized compiled scan (oracle + batched, and the
    sharded mirrors if parallel/lp_shard.py has been imported). The
    benchmark harness calls this between config cells so a sweep's peak
    memory is one cell's executables, not the whole matrix's."""
    import sys
    _compiled_window_cached.cache_clear()
    _compiled_batch_cached.cache_clear()
    lp_shard = sys.modules.get("repro.parallel.lp_shard")
    if lp_shard is not None:
        lp_shard._compiled_window_sharded.cache_clear()
        lp_shard._compiled_batch_sharded.cache_clear()


@functools.lru_cache(maxsize=COMPILED_CACHE_SIZE)
def _compiled_window_cached(cfg: EngineConfig, n_steps: int):
    if not cfg.obs.enabled:
        # telemetry off: this branch is chosen by a static Python `if`,
        # so the traced scan is byte-for-byte the historical one — no
        # ring carry, no callback; the one extra output is the window's
        # counter summary, reduced from the series after the scan
        def fn(state, mf):
            def body(s, _):
                return step(s, cfg, mf=mf)
            state, series = jax.lax.scan(body, state, None, length=n_steps)
            return state, series, window_summary(series)
        return jax.jit(fn)

    # telemetry on: thread a (drain_every, K) f32 ring through the scan
    # carry; each step writes its ledger row into slot t % drain_every,
    # and when the ring wraps one async unordered jax.debug.callback
    # ships the block to the host (repro.obs.runtime routes it to the
    # current session). The step itself is untouched — the ring write
    # reads counters the step already computed, and the PRNG stream
    # never sees the ring, so results stay bit-identical.
    de = cfg.obs.drain_every
    n_cols = len(obs_ledger.ledger_keys(cfg))

    def fn(state, mf):
        def body(carry, _):
            s, ring = carry
            s2, m = step(s, cfg, mf=mf)
            t = s["t"]  # the step that just executed
            ring = ring.at[t % de].set(obs_ledger.ledger_row(cfg, s2, m, t))
            jax.lax.cond(
                (t + 1) % de == 0,
                lambda r, tt: jax.debug.callback(obs_runtime.on_block,
                                                 r, tt, ordered=False),
                lambda r, tt: None,
                ring, t)
            return (s2, ring), m
        # -1 init: slots a short window never writes (and slots left
        # over from a previous window of this resident state) carry an
        # impossible step stamp, which the host-side stamp-match filter
        # drops — see Telemetry._ingest_stamped
        ring0 = jnp.full((de, n_cols), -1.0, jnp.float32)
        (s, ring), series = jax.lax.scan(body, (state, ring0), None,
                                         length=n_steps)
        return s, ring, series, window_summary(series)
    return jax.jit(fn)


def _compiled_window(cfg: EngineConfig, n_steps: int):
    """One jitted n_steps-scan per config shape, with MF dynamic. It
    returns (state, series, summary), `ring` before `series` with
    telemetry on: `summary` is the window's `window_summary`, so that
    the counters reach the host in one transfer.

    Eager `lax.scan` re-traces (and recompiles) on every call because
    the body closure is fresh each time; memoizing the jitted scan by
    the hashable config makes repeated runs — and the §5.5 tuner's
    per-window MF re-parameterization — reuse one executable. An MF
    sweep over otherwise-identical configs compiles exactly once (see
    window_key_cfg)."""
    return _compiled_window_cached(window_key_cfg(cfg), n_steps)


def walk_slots(cfg: EngineConfig) -> int:
    """Candidate slots the proximity walk of one step of one replica
    visits, on either execution layer (the sharded layer: the sum of
    every device's own walk) — the `walk_slots` of a window's dispatch
    span."""
    if cfg.sharding == "lp_device":
        from repro.parallel import lp_shard
        return lp_shard.walk_slots(cfg)
    return _abm.walk_slots(cfg.abm)


def _dispatch_window(state, cfg: EngineConfig, n_steps: int, mf=None):
    """Enqueue one n_steps window on either execution layer; returns
    (state, per-step series, counter summary) without waiting for the
    device (with telemetry on, after the ledger's tail is flushed)."""
    _trace_guard(state, cfg, n_steps)
    if cfg.sharding == "lp_device":
        from repro.parallel import lp_shard
        return lp_shard._scan_sharded(state, cfg, n_steps, mf=mf)

    mf_val = jnp.float32(cfg.heuristic.mf if mf is None else mf)
    if cfg.obs.enabled:
        t0 = int(state["t"])
        state, ring, series, summary = _compiled_window(cfg, n_steps)(
            state, mf_val)
        obs_runtime.flush_tail(ring, t0, t0 + n_steps)
        return state, series, summary
    return _compiled_window(cfg, n_steps)(state, mf_val)


def _run_window(state, cfg: EngineConfig, n_steps: int, mf=None):
    """Advance an existing state by n_steps; returns (state, counters).

    Used by the §5.5 intra-run self-tuner, which re-parameterizes the
    heuristic between windows — pass the window's MF via `mf` (a
    dynamic argument: no recompilation between windows). Sharded states
    (from a sharded init_engine) advance through the sharded step and
    stay slot-major."""
    state, series, summary = _dispatch_window(state, cfg, n_steps, mf=mf)
    return state, unpack(summary, series_shapes(series))


def _run(key, cfg: EngineConfig):
    """Run the full simulation; returns (final_state, stacked metrics,
    aggregate counters). With cfg.sharding="lp_device" the run executes
    LP-per-device on the JAX mesh (bit-identical result; extra
    halo_frac/shard_overflow metrics)."""
    check_trace_horizon(cfg.abm, 0, cfg.timesteps)
    if cfg.sharding == "lp_device":
        from repro.parallel import lp_shard
        return lp_shard.run_sharded(key, cfg)
    st, series, summary = _dispatch_window(_init_engine(key, cfg), cfg,
                                           cfg.timesteps)
    counters = unpack(summary, series_shapes(series))
    counters["migration_ratio"] = _migration_ratio(counters, cfg)
    return st, series, counters


# ---------------------------------------------------------------------------
# batched multi-replica execution (vmap over seeds)
# ---------------------------------------------------------------------------


def _migration_ratio(counters, cfg: EngineConfig) -> float:
    return counters["migrations"] / (cfg.abm.n_se *
                                     (cfg.timesteps / 1000.0))  # Eq. 8


def replica_keys(seeds):
    """Seeds (ints) -> one PRNG key per replica. A replica's key is
    exactly `jax.random.key(seed)`, so replica r of a batch reproduces
    a sequential `run(jax.random.key(seeds[r]), cfg)` bit-for-bit."""
    return [jax.random.key(int(s)) for s in seeds]


def stack_states(states):
    """Stack per-replica state pytrees along a new leading replica axis
    (PRNG keys included — key arrays stack like any other leaf)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _init_batch(cfg: EngineConfig, seeds):
    """Stacked engine state for R replicas: every leaf of the single-
    replica state gains a leading replica axis (including `t`, which
    stays lockstep across replicas — they advance together).

    The per-replica inits run through the very same (eager) init
    a sequential run uses, then stack — deliberately NOT a vmapped
    jitted init: jit fuses the clustered-mobility position arithmetic
    with FMA and drifts ULPs off the eager path, which would break the
    per-seed bit-identity contract (tests/test_replicas.py). Init is a
    one-off O(N) cost; the scan is where batching pays."""
    return stack_states([_init_engine(k, cfg) for k in replica_keys(seeds)])


def _mf_vector(cfg: EngineConfig, mf, n_rep: int):
    """Per-replica Migration Factors: scalar/None broadcasts; an (R,)
    array lets each replica run its own MF (the batched §5.5 tuner)."""
    mf = cfg.heuristic.mf if mf is None else mf
    return jnp.broadcast_to(jnp.asarray(mf, jnp.float32), (n_rep,))


def replica_series(series, r: int):
    """Slice replica r out of a batched (T, R, ...) metrics series,
    yielding the (T, ...) series a sequential run would have produced."""
    return {k: v[:, r] for k, v in series.items()}


@functools.lru_cache(maxsize=COMPILED_CACHE_SIZE)
def _compiled_batch_cached(cfg: EngineConfig, n_steps: int):
    def fn(states, mfs):
        def body(s, _):
            return jax.vmap(lambda st, m: step(st, cfg, mf=m))(s, mfs)
        states, series = jax.lax.scan(body, states, None, length=n_steps)
        return states, series, batch_summary(series)
    return jax.jit(fn)


def _compiled_batch(cfg: EngineConfig, n_steps: int):
    """One jitted batched scan per config shape: `jax.vmap` of the
    single-replica step over the leading replica axis, MF dynamic and
    per-replica; it returns (states, series, `batch_summary`). jit
    re-specializes per replica count, so the cache key stays (config
    shape, n_steps) like `_compiled_window`. Batched scans are
    un-instrumented (strip_obs): the ledger covers the single-replica
    resident paths."""
    return _compiled_batch_cached(window_key_cfg(strip_obs(cfg)), n_steps)


def _dispatch_window_batch(states, cfg: EngineConfig, n_steps: int,
                           mf=None):
    """Enqueue one batched n_steps window of R stacked replicas on
    either execution layer; returns (states, (T, R, ...) series,
    (R, K) counter summary) without waiting for the device."""
    _trace_guard(states, cfg, n_steps)
    if cfg.sharding == "lp_device":
        from repro.parallel import lp_shard
        return lp_shard._scan_batch_sharded(states, cfg, n_steps, mf=mf)
    n_rep = states["t"].shape[0]
    return _compiled_batch(cfg, n_steps)(states, _mf_vector(cfg, mf, n_rep))


def _run_window_batch(states, cfg: EngineConfig, n_steps: int, mf=None):
    """Advance R stacked replica states by n_steps in one batched scan.

    `mf` may be a scalar (all replicas) or an (R,) vector — the batched
    §5.5 tuner descends each replica's MF independently, so MF rides as
    a per-replica dynamic argument of the one compiled scan. Returns
    (states, [per-replica counters])."""
    states, series, summary = _dispatch_window_batch(states, cfg, n_steps,
                                                     mf=mf)
    return states, unpack_batch(summary, series_shapes(series))


def _run_batch(cfg: EngineConfig, seeds):
    """Run R independent replicas (one per seed) in a single batched
    device pass: `jax.vmap` over the leading seed axis of the memoized
    jitted scan. Heuristic windows, mobility state, pending migrations —
    the whole engine state — ride the batch axis, so replicas never
    interact; replica r is bit-identical to a sequential
    `jax.random.key(seeds[r])` run (tests/test_replicas.py).

    Returns (states, series, reps): stacked final states (leading
    replica axis), the batched per-step metrics series (T, R, ...), and
    one aggregate-counters dict per replica (the exact schema the
    single-replica runner returns, `migration_ratio` included). With
    cfg.sharding="lp_device" the batch axis is vmapped *inside* each
    shard (parallel/lp_shard.py), so sharded replicas stay bit-identical
    to oracle replicas per seed."""
    check_trace_horizon(cfg.abm, 0, cfg.timesteps)
    if cfg.sharding == "lp_device":
        from repro.parallel import lp_shard
        return lp_shard.run_batch_sharded(cfg, seeds)
    states, series, summary = _dispatch_window_batch(
        _init_batch(cfg, seeds), cfg, cfg.timesteps)
    reps = unpack_batch(summary, series_shapes(series))
    for c in reps:
        c["migration_ratio"] = _migration_ratio(c, cfg)
    return states, series, reps


# ---------------------------------------------------------------------------
# deprecated free-function API (PR 8): the six runners collapsed into
# the repro.core.Engine facade (core/service.py). The shims delegate so
# old callers keep their exact bits; new code goes through Engine.
# ---------------------------------------------------------------------------


def _deprecated(old: str, hint: str):
    import warnings
    warnings.warn(
        f"repro.core.engine.{old} is deprecated; use {hint} "
        "(see README §Service API)",
        DeprecationWarning, stacklevel=3)


def init_engine(key, cfg: EngineConfig):
    """Deprecated: use `repro.core.Engine(cfg).init(seed=...)`."""
    _deprecated("init_engine", "repro.core.Engine(cfg).init()")
    return _init_engine(key, cfg)


def run_window(state, cfg: EngineConfig, n_steps: int, mf=None):
    """Deprecated: use `repro.core.Engine.step(n, mf=...)`."""
    _deprecated("run_window", "repro.core.Engine.step(n)")
    return _run_window(state, cfg, n_steps, mf=mf)


def run(key, cfg: EngineConfig):
    """Deprecated: use `repro.core.Engine(cfg).init().step(...)`."""
    _deprecated("run", "repro.core.Engine(cfg).run()")
    return _run(key, cfg)


def init_batch(cfg: EngineConfig, seeds):
    """Deprecated: use `repro.core.Engine(cfg).init(seeds=[...])`."""
    _deprecated("init_batch", "repro.core.Engine(cfg).init(seeds=[...])")
    return _init_batch(cfg, seeds)


def run_window_batch(states, cfg: EngineConfig, n_steps: int, mf=None):
    """Deprecated: use `repro.core.Engine.step(n, mf=...)` on a batched
    Engine (`init(seeds=[...])`)."""
    _deprecated("run_window_batch", "repro.core.Engine.step(n)")
    return _run_window_batch(states, cfg, n_steps, mf=mf)


def run_batch(cfg: EngineConfig, seeds):
    """Deprecated: use `repro.core.Engine(cfg).run(seeds=[...])`."""
    _deprecated("run_batch", "repro.core.Engine(cfg).run(seeds=[...])")
    return _run_batch(cfg, seeds)
