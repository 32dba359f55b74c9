"""Resident engine service: the unified stepping API (PR 8).

Historically the engine was a batch artifact — six free functions
(`run`, `run_window`, `run_batch`, `init_engine`, `init_batch`,
`run_window_batch`) that init, scan, and return. This module makes the
engine a *resident service* around the same memoized jitted scans:

- `Engine` — one facade over init / stepping / open-world churn /
  device-state queries, on both execution layers ("none" and
  "lp_device") and both replica shapes (single seed or a batch). The
  state stays on device between calls; `step` windows reuse the
  compiled-scan memo, so an interactive session pays tracing once.

- **Open-world churn** (cfg.open_world): `arrive(rows)` / `depart(ids)`
  are O(batch) in-device slot updates — the oracle keeps a fixed
  universe of `abm.n_se` slots with `lp >= 0` marking live rows (the
  generalization of the sharded layer's `gid >= 0` free-slot
  machinery), and the sharded layer packs arrivals into per-device free
  slots exactly like cross-device migrations land. Exact-or-loud: a
  batch that outgrows the free pool (or a device's `shard_capacity`)
  raises before (or without) corrupting state. With zero churn and a
  full population the trajectory is bit-identical to the closed-world
  engine on both layers (tests/test_service.py).

- **Queries** served from device state — `query_neighbors` (the PR 7
  CSR cell list, reused as a read-only index), `query_lcr` (the
  would-be flow matrix if every live SE sent now), `query_region`
  (wrap-aware bbox filter). No unshard: sharded queries run on the
  slot-major global view.

- `ReplicaService` — request multiplexing over the PR 5 batch axis:
  R resident replica slots advance together in batched windows sized
  to the nearest request boundary (continuous batching); a finished
  slot is refilled from the queue while the others keep their state,
  so the device never idles between requests. Each request's merged
  counters are exactly what a solo run of that seed reports.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import engine as _eng
from repro.core import neighbors
from repro.core.abm import interaction_counts_overflow
from repro.core.engine import EngineConfig
from repro.core.stats import merge_counters
from repro.obs import runtime as obs_runtime
from repro.obs.ledger import Telemetry
from repro.obs.trace import Spans


def _pad_pow2(b: int) -> int:
    """Round a churn batch up to a power of two so repeated interactive
    batches of drifting sizes hit a handful of compiled shapes."""
    return 1 << max(0, b - 1).bit_length()


_jit_oracle_arrive = jax.jit(_eng.oracle_arrive)
_jit_oracle_depart = jax.jit(_eng.oracle_depart)


class Engine:
    """Resident facade over the GAIA engine (see module docstring).

    >>> eng = Engine(cfg).init(seed=0)
    >>> eng.step(200)                      # window counters
    >>> ids = eng.arrive({"pos": new_pos}) # open_world only
    >>> eng.query_neighbors(ids[:2])
    >>> eng.metrics()                      # accumulated run counters

    Batched replicas: `init(seeds=[...])` — `step` then returns one
    counters dict per replica. Churn and queries are single-replica
    (they address one resident world); a batched engine raises on them.
    """

    def __init__(self, cfg: EngineConfig, obs_sinks=None):
        self.cfg = cfg
        self.state = None
        self._batched = False
        self._parts = []  # per-window counters (or lists, batched)
        self._weights = []
        self._steps = 0
        self._live = set()
        self._free = []
        # telemetry session (cfg.obs.enabled): the ledger fills from the
        # device ring drain during single-replica `step` windows (the
        # batched scans stay un-instrumented — engine.strip_obs), the
        # event log additionally hears churn batches and tuner moves
        # host-side. Compiled executables are shared across Engine
        # instances, so the session is re-asserted current around every
        # windowed call (repro.obs.runtime routing).
        self.telemetry = (Telemetry(cfg, sinks=obs_sinks)
                          if cfg.obs.enabled else None)
        # program spans of every call (repro.obs.trace): one call id per
        # call, shared by its stages
        self._spans = Spans()

    # -- lifecycle -------------------------------------------------------

    def init(self, seeds=None, *, seed: int = 0) -> "Engine":
        """Materialize resident device state: one replica from `seed`,
        or R stacked replicas from `seeds` (overrides `seed`)."""
        if seeds is not None:
            self.state = _eng._init_batch(self.cfg, list(seeds))
            self._batched = True
        else:
            self.state = _eng._init_engine(jax.random.key(int(seed)),
                                           self.cfg)
            self._batched = False
        self._parts, self._weights, self._steps = [], [], 0
        # the proximity walk's candidate slots per step of all replicas
        # (a step's `walk_slots`)
        self._walk = _eng.walk_slots(self.cfg) * (
            self.state["t"].shape[0] if self._batched else 1)
        live = self.cfg.initial_live()
        self._live = set(range(live))
        self._free = list(range(self.cfg.abm.n_se - 1, live - 1, -1))
        return self

    def run(self, seeds=None, *, seed: int = 0):
        """One-shot convenience (the old `run` / `run_batch` contract):
        returns (final_state, per-step series, counters) — counters is a
        list with `seeds`. Does not touch this engine's resident
        state."""
        if self.telemetry is not None:
            obs_runtime.set_current(self.telemetry)
        if seeds is not None:
            # batched scans are un-instrumented (engine.strip_obs): the
            # ledger covers the single-replica paths
            return _eng._run_batch(self.cfg, list(seeds))
        return _eng._run(jax.random.key(int(seed)), self.cfg)

    def _require_state(self):
        if self.state is None:
            raise RuntimeError("Engine.init() first — no resident state")

    def _single(self, what: str):
        self._require_state()
        if self._batched:
            raise RuntimeError(
                f"{what} addresses one resident world; this Engine holds "
                "a replica batch (init(seed=...) for a single one)")

    # -- stepping --------------------------------------------------------

    def step(self, n: int = 1, mf=None):
        """Advance the resident state n timesteps through the memoized
        compiled window scan. Returns this window's counters (a list of
        per-replica dicts when batched) and accumulates them into
        `metrics()`. `mf` overrides the Migration Factor for the window
        (per-replica vector allowed when batched) — the §5.5 tuners'
        contract, unchanged.

        The window program computes its own counter summary
        (`engine.window_summary`, one flat buffer); its copy to the host
        starts as soon as the window is enqueued, and the counters are
        unpacked from that one transfer.

        Spans: `gaia.step` (n steps) holds `dispatch` (the window
        program enqueued and the summary's copy started; `walk_slots`
        per step), `wait` (until the summary is ready) and `readback`
        (the summary read and unpacked; `fetches` device-to-host
        transfers, 1)."""
        self._require_state()
        if self.telemetry is not None:
            obs_runtime.set_current(self.telemetry)
        if self._batched:
            dispatch, unpack = _eng._dispatch_window_batch, _eng.unpack_batch
        else:
            dispatch, unpack = _eng._dispatch_window, _eng.unpack
        sp = self._spans
        with sp.call("step", n=n):
            with sp.child("dispatch", walk_slots=self._walk):
                self.state, series, summary = dispatch(self.state, self.cfg,
                                                       n, mf=mf)
                summary.copy_to_host_async()
            with sp.child("wait"):
                summary.block_until_ready()
            with sp.child("readback") as span:
                read = _eng.HostReads()
                counters = unpack(read(summary), _eng.series_shapes(series))
                span.set_metadata(fetches=read.count)
        self._parts.append(counters)
        self._weights.append(n)
        self._steps += n
        return counters

    def metrics(self) -> dict:
        """Counters accumulated over every `step` window so far, plus
        the Eq. 8 migration_ratio over the stepped span (a list of
        per-replica dicts when batched)."""
        self._require_state()
        if not self._parts:
            return [] if self._batched else {}
        per_k = self.cfg.abm.n_se * (max(self._steps, 1) / 1000.0)
        if self._batched:
            out = []
            for r in range(len(self._parts[0])):
                c = merge_counters([p[r] for p in self._parts],
                                   self._weights)
                c["migration_ratio"] = c["migrations"] / per_k
                out.append(c)
            return out
        c = merge_counters(self._parts, self._weights)
        c["migration_ratio"] = c["migrations"] / per_k
        return c

    # -- telemetry views (cfg.obs.enabled) -------------------------------

    def _require_obs(self, what: str):
        if self.telemetry is None:
            raise RuntimeError(
                f"{what} needs EngineConfig(obs=ObsConfig(enabled=True))")

    def ledger(self):
        """The per-step :class:`~repro.obs.ledger.MetricsLedger` filled
        by the device ring drain (rows()/column()/summary()/latest())."""
        self._require_obs("ledger")
        return self.telemetry.ledger

    def events(self, kind=None) -> list:
        """Telemetry events recorded so far, newest last, optionally
        filtered by kind (see repro.obs.events.EVENT_KINDS)."""
        self._require_obs("events")
        return self.telemetry.events.records(kind)

    def prometheus(self) -> str:
        """Prometheus text exposition of the session: latest per-step
        gauges + whole-run means from the ledger, event counts, and the
        facade's own occupancy."""
        from repro.obs.prom import prometheus_text
        self._require_obs("prometheus")
        extra = {"steps_total": self._steps}
        if self.cfg.open_world:
            extra["population"] = self.population()
        return prometheus_text(self.telemetry, extra=extra)

    def close(self) -> None:
        """Flush and close telemetry sinks (file sinks in particular);
        the engine remains usable, events simply stop being written to
        closed sinks."""
        if self.telemetry is not None:
            if obs_runtime.get_current() is self.telemetry:
                obs_runtime.set_current(None)
            self.telemetry.close()

    # -- open-world churn ------------------------------------------------

    def _require_open(self, what: str):
        self._single(what)
        if not self.cfg.open_world:
            raise RuntimeError(
                f"{what} needs EngineConfig(open_world=True)")

    def population(self) -> int:
        """Live SEs (host-side view of the free-slot pool)."""
        return len(self._live)

    def live_ids(self) -> list:
        """Sorted ids of the live SEs (the valid depart targets)."""
        return sorted(self._live)

    def arrive(self, rows) -> list:
        """Admit a batch of SEs. `rows["pos"]` (B, 2) is required;
        optional "lp" (default: the x-stripe LP of the position),
        "waypoint", "mob", "epi" (infection flag, default susceptible).
        Returns the B assigned SE ids. Raises
        RuntimeError, state untouched, if the universe has fewer than B
        free slots; on the sharded layer a destination device without a
        free slot raises too (naming shard_capacity), with the admitted
        prefix of the batch applied and reported.

        Spans: `gaia.arrive` (`batch`, `padded` rows) holds `prepare`
        (padding, free-slot bookkeeping), `apply` (the jitted update)
        and, on the sharded layer, `sync` (the admission mask read)."""
        import numpy as np
        self._require_open("arrive")
        sp = self._spans
        with sp.call("arrive") as call:
            with sp.child("prepare"):
                pos = np.asarray(rows["pos"], np.float32).reshape(-1, 2)
                b = pos.shape[0]
                if b == 0:
                    return []
                if b > len(self._free):
                    raise RuntimeError(
                        f"arrive: batch of {b} exceeds the "
                        f"{len(self._free)} free slots of the "
                        f"n_se={self.cfg.abm.n_se} universe; raise "
                        "abm.n_se (the slot universe) or depart SEs first")
                ids, pad_ids, prows = self._pad_arrivals(rows, pos)
                call.set_metadata(batch=b, padded=len(pad_ids))
            self._admit(ids, pad_ids, prows)
        return ids

    def _pad_arrivals(self, rows, pos):
        """Take free slots for the arrivals at `pos` and pad the batch
        to a power of two: (ids, padded ids, padded rows)."""
        import numpy as np
        b = pos.shape[0]
        abm = self.cfg.abm
        if "lp" in rows:
            lps = np.asarray(rows["lp"], np.int32).reshape(-1)
        else:
            lps = np.clip((pos[:, 0] / abm.area * abm.n_lp).astype(
                np.int32), 0, abm.n_lp - 1)
        ids = [self._free.pop() for _ in range(b)]
        bp = _pad_pow2(b)
        pad_ids = np.full((bp,), -1, np.int32)
        pad_ids[:b] = ids
        pad_pos = np.zeros((bp, 2), np.float32)
        pad_pos[:b] = pos
        pad_lp = np.zeros((bp,), np.int32)
        pad_lp[:b] = lps
        prows = {"pos": pad_pos, "lp": pad_lp}
        for k in ("waypoint", "mob"):
            if k in rows:
                buf = np.zeros((bp, 2), np.float32)
                buf[:b] = np.asarray(rows[k], np.float32).reshape(-1, 2)
                prows[k] = buf
        if "epi" in rows:
            buf = np.zeros((bp,), np.int32)
            buf[:b] = np.asarray(rows["epi"], np.int32).reshape(-1)
            prows["epi"] = buf
        return ids, pad_ids, prows

    def _admit(self, ids, pad_ids, prows) -> None:
        """Write padded arrivals into their slots; the sharded layer
        reads back which were admitted and raises for the refused."""
        import numpy as np
        sp = self._spans
        b = len(ids)
        if self.cfg.sharding == "lp_device":
            from repro.parallel import lp_shard
            with sp.child("apply"):
                self.state, adm = lp_shard.arrive_sharded(
                    self.state, self.cfg, pad_ids, prows)
            with sp.child("sync"):
                adm = np.asarray(adm)[:b]
            if not adm.all():
                refused = [i for i, ok in zip(ids, adm) if not ok]
                self._free.extend(reversed(refused))
                admitted = [i for i, ok in zip(ids, adm) if ok]
                self._live.update(admitted)
                raise RuntimeError(
                    f"arrive: {len(refused)} of {b} arrivals refused — "
                    "their destination devices have no free slot; raise "
                    "EngineConfig.shard_capacity (admitted: "
                    f"{len(admitted)} rows, already applied)")
        else:
            with sp.child("apply"):
                self.state = _jit_oracle_arrive(self.state, pad_ids, prows)
        self._live.update(ids)
        if self.telemetry is not None:
            self.telemetry.emit("arrive", self._steps, count=b,
                                population=len(self._live))

    def depart(self, ids) -> None:
        """Remove the SEs `ids` (an O(batch) in-device update). Their
        slots return to the free pool. Raises KeyError, state untouched,
        if any id is not live.

        Spans: `gaia.depart` (`batch`, `padded` ids) holds `prepare`,
        `apply` and, on the sharded layer, `sync`, as `arrive` does."""
        import numpy as np
        self._require_open("depart")
        sp = self._spans
        with sp.call("depart") as call:
            with sp.child("prepare"):
                ids = [int(i) for i in ids]
                if not ids:
                    return
                missing = [i for i in ids if i not in self._live]
                if missing or len(set(ids)) != len(ids):
                    raise KeyError(
                        f"depart: not live (or duplicated in batch): "
                        f"{sorted(set(missing or ids))[:8]}")
                b = len(ids)
                pad_ids = np.full((_pad_pow2(b),), -1, np.int32)
                pad_ids[:b] = ids
                call.set_metadata(batch=b, padded=len(pad_ids))
            if self.cfg.sharding == "lp_device":
                from repro.parallel import lp_shard
                with sp.child("apply"):
                    self.state, found = lp_shard.depart_sharded(
                        self.state, self.cfg, pad_ids)
                with sp.child("sync"):
                    found = np.asarray(found)[:b]
                if not found.all():
                    raise RuntimeError(
                        "depart: live-set bookkeeping and device state "
                        "disagree — some ids were not found in any slot")
            else:
                with sp.child("apply"):
                    self.state = _jit_oracle_depart(self.state, pad_ids)
        self._live.difference_update(ids)
        self._free.extend(reversed(ids))
        if self.telemetry is not None:
            self.telemetry.emit("depart", self._steps, count=b,
                                population=len(self._live))

    # -- device-state queries -------------------------------------------

    def _universe(self):
        """(pos, lp, ext, valid) on the slot universe — id-order for the
        oracle (ext = arange), slot-major for the sharded layer
        (ext = gid). Queries never unshard."""
        st = self.state
        if self.cfg.sharding == "lp_device":
            ext = st["gid"]
            return st["pos"], st["lp"], ext, ext >= 0
        n = self.cfg.abm.n_se
        ext = jnp.arange(n, dtype=jnp.int32)
        return st["pos"], st["lp"], ext, st["lp"] >= 0

    def query_neighbors(self, ids) -> dict:
        """{id: sorted list of live SE ids within interaction_range} —
        served from device state via the CSR cell list (dense fallback
        when the world is too small to tessellate). Raises KeyError for
        ids that are not live.

        Spans: `gaia.query_neighbors` (`ids`) holds `issue` (the eager
        programs enqueued: `grid`, the cell list, and `walk`, the
        candidate test), `wait` and `readback` (transfer, dict, sort)."""
        self._single("query_neighbors")
        ids = [int(i) for i in ids]
        missing = [i for i in ids if i not in self._live]
        if missing:
            raise KeyError(f"query_neighbors: not live: {missing[:8]}")
        if not ids:
            return {}
        abm = self.cfg.abm
        sp = self._spans
        with sp.call("query_neighbors", ids=len(ids)):
            with sp.child("issue"):
                pos, lp, ext, valid = self._universe()
                q = jnp.asarray(ids, jnp.int32)
                if self.cfg.sharding == "lp_device":
                    rows = jnp.argmax(ext[None, :] == q[:, None], axis=1)
                else:
                    rows = q
                rows = rows.astype(jnp.int32)
                qpos = pos[rows]
                spec = abm.grid_spec() if abm.resolved_backend() in (
                    "grid", "pallas_grid") else None
                if spec is not None:
                    with sp.child("grid"):
                        grid = neighbors.build_grid(pos, spec, valid=valid,
                                                    with_table=False)
                    with sp.child("walk"):
                        cols = neighbors.rows_grid_neighbor_ids(
                            pos, abm.area, abm.interaction_range, spec,
                            grid, qpos, rows)
                else:
                    with sp.child("walk"):
                        d2 = neighbors.toroidal_d2(qpos[:, None, :],
                                                   pos[None, :, :], abm.area)
                        r2 = abm.interaction_range * abm.interaction_range
                        j = jnp.arange(pos.shape[0], dtype=jnp.int32)
                        ok = (valid[None, :] & (d2 <= r2)
                              & (j[None, :] != rows[:, None]))
                        cols = jnp.where(ok, j[None, :], -1)
                nbr = jnp.where(cols >= 0, ext[jnp.clip(cols, 0, None)], -1)
            with sp.child("wait"):
                jax.block_until_ready(nbr)
            with sp.child("readback"):
                import numpy as np
                nbr = np.asarray(nbr)
                return {i: sorted(int(x) for x in row if x >= 0)
                        for i, row in zip(ids, nbr)}

    def query_lcr(self) -> float:
        """Instantaneous LCR of the current placement: the fraction of
        interactions that would be LP-local if every live SE sent now —
        the heuristics' objective read off device state, no stepping.

        Spans: `gaia.query_lcr` holds `issue` (`counts`, the proximity
        walk, and `flows`, the LP flow matrix and its ratio), `wait` and
        `readback`."""
        self._single("query_lcr")
        abm = self.cfg.abm
        sp = self._spans
        with sp.call("query_lcr"):
            with sp.child("issue"):
                pos, lp, ext, valid = self._universe()
                with sp.child("counts"):
                    counts, _ = interaction_counts_overflow(
                        pos, lp, valid, abm, valid=valid)
                with sp.child("flows"):
                    safe_lp = jnp.clip(lp, 0, abm.n_lp - 1)
                    flows = jnp.zeros((abm.n_lp, abm.n_lp), jnp.int32).at[
                        safe_lp].add(counts)
                    total = flows.sum()
                    lcr = jnp.trace(flows) / jnp.maximum(total, 1)
            with sp.child("wait"):
                jax.block_until_ready(lcr)
            with sp.child("readback"):
                return float(lcr)

    def query_region(self, bbox) -> list:
        """Sorted live SE ids with position inside `bbox` = (x0, y0,
        x1, y1), inclusive and wrap-aware per axis (x0 > x1 selects the
        interval wrapping through the torus seam).

        Spans: `gaia.query_region` holds `issue` (the filter and the
        selection, whose size the host learns), `wait` and `readback`."""
        self._single("query_region")
        x0, y0, x1, y1 = (float(v) for v in bbox)

        def axis(v, lo, hi):
            if lo <= hi:
                return (v >= lo) & (v <= hi)
            return (v >= lo) | (v <= hi)

        sp = self._spans
        with sp.call("query_region"):
            with sp.child("issue"):
                pos, lp, ext, valid = self._universe()
                hit = valid & axis(pos[:, 0], x0, x1) & axis(pos[:, 1], y0,
                                                             y1)
                sel = ext[hit]
            with sp.child("wait"):
                jax.block_until_ready(sel)
            with sp.child("readback"):
                import numpy as np
                return sorted(int(i) for i in np.asarray(sel))


class ReplicaService:
    """Continuous batching of independent simulation requests over the
    replica axis.

    R resident slots share one batched compiled scan; `submit` enqueues
    (seed, steps, mf) requests and `drain` advances all slots together
    in windows sized to the nearest request boundary, refilling each
    finished slot from the queue (the other slots keep their state and
    their own t — per-slot time rides the batch axis). A request's
    merged counters are exactly a solo run's: the batched step is
    bit-identical per replica (PR 5), and window merging preserves the
    counter sums (stats.merge_counters).
    """

    def __init__(self, cfg: EngineConfig, n_slots: int):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        self.cfg = cfg
        self.n_slots = n_slots
        self._queue = []  # pending (rid, seed, steps, mf)
        self._next_rid = 0
        self.results = {}

    def submit(self, seed: int, steps: int, mf=None) -> int:
        """Enqueue a request; returns its request id (the `results`
        key after `drain`)."""
        if steps < 1:
            raise ValueError("steps must be >= 1")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, int(seed), int(steps), mf))
        return rid

    def prometheus(self) -> str:
        """Prometheus text exposition of the service: queue depth, slot
        count, completed-request count, and the mean LCR / migrations
        over completed requests. The replica scans are un-instrumented
        (the per-step ledger covers single-replica Engines), so this
        reports request-level aggregates only."""
        lines = []

        def gauge(name, value):
            name = f"gaia_service_{name}"
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {value:g}")

        gauge("slots", self.n_slots)
        gauge("queue_depth", len(self._queue))
        gauge("requests_completed", len(self.results))
        done = list(self.results.values())
        if done:
            gauge("mean_lcr", sum(c["mean_lcr"] for c in done) / len(done))
            gauge("mean_migrations",
                  sum(c["migrations"] for c in done) / len(done))
        return "\n".join(lines) + "\n"

    @staticmethod
    def _set_replica(states, r: int, sub):
        """Overwrite replica r of a stacked state with a fresh
        single-replica state (PRNG-key leaves routed through
        key_data/wrap_key_data — typed keys have no .at updates)."""
        def setr(b, s):
            if jnp.issubdtype(b.dtype, jax.dtypes.prng_key):
                bd = jax.random.key_data(b)
                return jax.random.wrap_key_data(
                    bd.at[r].set(jax.random.key_data(s)))
            return b.at[r].set(s)
        return jax.tree.map(setr, states, sub)

    def drain(self) -> dict:
        """Run every queued request to completion; returns {rid:
        counters} (also kept in `self.results`). Idle slots (queue
        exhausted) ride along and are discarded."""
        if not self._queue:
            return self.results
        R = self.n_slots
        slot = [None] * R  # per-slot [rid, remaining, mf, parts, weights]
        states = None

        def refill(states, r):
            rid, seed, steps, mf = self._queue.pop(0)
            sub = _eng._init_engine(jax.random.key(seed), self.cfg)
            if states is None:
                states = _eng.stack_states([sub] * R)
            else:
                states = self._set_replica(states, r, sub)
            slot[r] = [rid, steps, mf, [], []]
            return states

        for r in range(R):
            if self._queue:
                states = refill(states, r)
        while any(s is not None for s in slot):
            active = [s for s in slot if s is not None]
            chunk = min(s[1] for s in active)
            mfs = jnp.asarray(
                [float(s[2] if s is not None and s[2] is not None
                       else self.cfg.heuristic.mf) for s in slot],
                jnp.float32)
            states, counters = _eng._run_window_batch(
                states, self.cfg, chunk, mf=mfs)
            for r in range(R):
                if slot[r] is None:
                    continue
                slot[r][3].append(counters[r])
                slot[r][4].append(chunk)
                slot[r][1] -= chunk
                if slot[r][1] == 0:
                    rid, _, _, parts, weights = slot[r]
                    c = merge_counters(parts, weights)
                    c["migration_ratio"] = c["migrations"] / (
                        self.cfg.abm.n_se * (sum(weights) / 1000.0))
                    self.results[rid] = c
                    slot[r] = None
                    if self._queue:
                        states = refill(states, r)
        return self.results
