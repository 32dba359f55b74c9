"""Plain reference of the simulated world, written from the model's
description and not from the program under test.

The model (arXiv:1610.01295, sections 4 and 5.1): N simulated entities
(SEs) do Random Waypoint on a square torus at a fixed speed; every step
each SE sends with probability pi, and a send reaches every other live
SE within the interaction range. SEs are spread over L logical
processes (LPs). GAIA heuristic #1 keeps, per SE, the per-LP counts of
its deliveries over the last kappa steps; an SE whose count towards its
busiest remote LP (eps) over its local count (iota) exceeds MF, and
that has not migrated for MT steps, asks to migrate there. A symmetric
balancer grants pairwise swaps and ring rotations between LPs, the
highest ratios first (ties by id), and a granted SE becomes active on
its new LP `migration_delay` steps later.

Randomness is drawn from `jax.random` under the documented key schedule
of a seed: the same seed draws the same numbers here and in any
implementation of the same semantics. Proximity is the full O(N^2)
pairwise test in row blocks: no cell list, no kernel, no sharding.

`dtype` selects the precision of positions: float32 is the reference;
a lower one is the control that the comparison must reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: rows per block of the pairwise proximity test
BLOCK = 1000


class World:
    """One resident world in id order. `cfg` is the configuration file's
    dict; `n_active` live SEs at the start (the rest are free slots)."""

    def __init__(self, cfg: dict, seed: int, n_active: int = 0,
                 dtype=jnp.float32, device=None):
        m = cfg["abm"]
        h = cfg["heuristic"]
        if (m.get("mobility", "rwp") != "rwp"
                or m.get("partitioner", "random") != "random"
                or m.get("workload", "none") != "none"
                or h.get("kind", 1) != 1
                or cfg.get("balance", "symmetric") != "symmetric"
                or cfg.get("repartition_every", 0) != 0):
            raise NotImplementedError(
                "the reference models RWP, the random initial partition, "
                "heuristic #1 and the symmetric balancer only")
        self.n, self.L = int(m["n_se"]), int(m["n_lp"])
        self.area = float(m["area"])
        self.speed = float(m["speed"])
        self.rng = float(m["interaction_range"])
        self.pi = float(m["p_interact"])
        self.kappa = int(h.get("kappa", 10))
        self.mt = int(h["mt"])
        self.mf = float(h["mf"])
        self.gaia = bool(cfg.get("gaia_on", True))
        self.delay = int(cfg.get("migration_delay", 5))
        self.dtype = dtype
        self.device = device or jax.devices()[0]
        with jax.default_device(self.device):
            self.st = self._init(seed, n_active or self.n)
        self._step = jax.jit(self._step_fn)
        self._counts_jit = jax.jit(self._counts)

    # -- state -----------------------------------------------------------

    def _init(self, seed: int, live: int):
        n, L = self.n, self.L
        k_model, k_run = jax.random.split(jax.random.key(seed))
        k_pos, k_wp, k_lp = jax.random.split(k_model, 3)
        pos = jax.random.uniform(k_pos, (n, 2), maxval=self.area)
        wp = jax.random.uniform(k_wp, (n, 2), maxval=self.area)
        lp = jax.random.permutation(k_lp, jnp.arange(n) % L).astype(
            jnp.int32)
        lp = jnp.where(jnp.arange(n) < live, lp, -1)
        return {
            "pos": pos.astype(self.dtype), "waypoint": wp.astype(self.dtype),
            "lp": lp,
            "pending_dst": jnp.full((n,), -1, jnp.int32),
            "pending_eta": jnp.full((n,), -1, jnp.int32),
            "ring": jnp.zeros((self.kappa, n, L), jnp.int32),
            "last_mig": jnp.full((n,), -10**6, jnp.int32),
            "key": k_run, "t": jnp.int32(0),
        }

    def numpy_state(self) -> dict:
        """The state on the host, key as its raw data."""
        out = {k: np.asarray(v) for k, v in self.st.items() if k != "key"}
        out["key"] = np.asarray(jax.random.key_data(self.st["key"]))
        return out

    # -- one step --------------------------------------------------------

    def _counts(self, pos, lp, sender, valid):
        """counts[i, l]: live SEs on LP l within range of sender i, self
        excluded; zero rows for non-senders."""
        n, L = self.n, self.L
        nb = -(-n // BLOCK)
        pad = nb * BLOCK - n
        rows = jnp.arange(nb * BLOCK, dtype=jnp.int32)
        bpos = jnp.pad(pos, ((0, pad), (0, 0))).reshape(nb, BLOCK, 2)
        bsend = jnp.pad(sender, (0, pad)).reshape(nb, BLOCK)
        ids = jnp.arange(n, dtype=jnp.int32)
        onehot = (lp[:, None] == jnp.arange(L)[None, :]) & valid[:, None]

        def block(args):
            p, s, r = args
            d = jnp.abs(p[:, None, :] - pos[None, :, :])
            d = jnp.minimum(d, self.area - d)
            d2 = d[..., 0] ** 2 + d[..., 1] ** 2
            hit = (d2 <= self.rng * self.rng) & (r[:, None] != ids[None, :])
            hit = hit & s[:, None]
            return jnp.stack([(hit & onehot[None, :, l]).sum(
                1, dtype=jnp.int32) for l in range(L)], axis=1)

        out = jax.lax.map(block, (bpos, bsend, rows.reshape(nb, BLOCK)))
        return out.reshape(nb * BLOCK, L)[:n]

    def _grants(self, cmat):
        """Symmetric balancer: swaps, then rotations around the ring of
        LPs at every shift, then swaps on what is left."""
        L = self.L
        off = 1 - jnp.eye(L, dtype=cmat.dtype)
        want = cmat * off
        g = jnp.minimum(want, want.T) * off
        left = want - g
        s = jnp.arange(L)
        for k in range(1, L):
            f = left[s, (s + k) % L].min()
            g = g.at[s, (s + k) % L].add(f)
            left = left.at[s, (s + k) % L].add(-f)
        return g + jnp.minimum(left, left.T) * off

    def _step_fn(self, st, mf):
        n, L, area, dt = self.n, self.L, self.area, self.dtype
        t = st["t"]
        key, k_move, k_send = jax.random.split(st["key"], 3)
        due = st["pending_eta"] == t
        lp = jnp.where(due, st["pending_dst"], st["lp"])
        pdst = jnp.where(due, -1, st["pending_dst"])
        peta = jnp.where(due, -1, st["pending_eta"])
        valid = lp >= 0

        # Random Waypoint: step `speed` along the shortest torus path to
        # the waypoint; on reaching it, take a fresh uniform waypoint
        pos, wp = st["pos"], st["waypoint"]
        fresh = jax.random.uniform(k_move, (n, 2), maxval=area).astype(dt)
        d = wp - pos
        d = jnp.where(d > area / 2, d - area, d)
        d = jnp.where(d < -area / 2, d + area, d)
        dist = jnp.linalg.norm(d, axis=-1, keepdims=True)
        reach = dist[:, 0] <= self.speed
        unit = jnp.where(dist > 0, d / jnp.maximum(dist, 1e-9), 0.0)
        nxt = jnp.where(reach[:, None], wp, (pos + unit * self.speed) % area)
        nxt = (nxt % area).astype(dt)
        nwp = jnp.where(reach[:, None], fresh, wp).astype(dt)
        pos = jnp.where(valid[:, None], nxt, pos)
        wp = jnp.where(valid[:, None], nwp, wp)
        sender = jax.random.bernoulli(k_send, self.pi, (n,)) & valid

        counts = self._counts(pos, lp, sender, valid)
        own = jnp.clip(lp, 0, L - 1)
        flows = jnp.zeros((L, L), jnp.int32).at[own].add(counts)
        local = jnp.trace(flows)
        total = flows.sum()

        ring = st["ring"].at[t % self.kappa].set(
            jnp.where(sender[:, None], counts, 0))
        last_mig = st["last_mig"]
        migs = jnp.int32(0)
        mig_flows = jnp.zeros((L, L), jnp.int32)
        if self.gaia:
            win = ring.sum(0)
            iota = jnp.take_along_axis(win, own[:, None], 1)[:, 0]
            ext = jnp.where(jnp.arange(L)[None, :] == own[:, None], 0, win)
            eps = ext.max(-1)
            dest = ext.argmax(-1).astype(jnp.int32)
            ratio = eps.astype(jnp.float32) / jnp.maximum(iota, 1).astype(
                jnp.float32)
            ask = (valid & (t - last_mig >= self.mt) & (ratio > mf)
                   & (eps > 0) & (pdst < 0))
            pair = jnp.where(ask, own * L + dest, L * L)
            cmat = jnp.bincount(pair, length=L * L + 1)[:-1].reshape(L, L)
            quota = self._grants(cmat).reshape(-1)
            order = jnp.lexsort((jnp.arange(n), -ratio, pair))
            sp = pair[order]
            first = jnp.searchsorted(sp, sp, side="left")
            rank = jnp.arange(n) - first
            ok_sorted = (sp < L * L) & (
                rank < quota[jnp.minimum(sp, L * L - 1)])
            admit = jnp.zeros((n,), bool).at[order].set(ok_sorted)
            pdst = jnp.where(admit, dest, pdst)
            peta = jnp.where(admit, t + self.delay, peta)
            last_mig = jnp.where(admit, t, last_mig)
            migs = admit.sum(dtype=jnp.int32)
            mig_flows = mig_flows.at[own, dest].add(admit.astype(jnp.int32))
        new = {"pos": pos, "waypoint": wp, "lp": lp, "pending_dst": pdst,
               "pending_eta": peta, "ring": ring, "last_mig": last_mig,
               "key": key, "t": t + 1}
        met = {"local_msgs": local, "remote_msgs": total - local,
               "migrations": migs,
               "heu_evals": valid.sum(dtype=jnp.int32) if self.gaia
               else jnp.int32(0),
               "lcr": local.astype(jnp.float32)
               / jnp.maximum(total.astype(jnp.float32), 1.0),
               "lp_flows": flows, "mig_flows": mig_flows}
        return new, met

    def step(self, n_steps: int, mf=None) -> dict:
        """Advance n steps; returns the window's counters: integer sums,
        `lcr` as the per-step float32 values."""
        mf = jnp.float32(self.mf if mf is None else mf)
        mets = []
        with jax.default_device(self.device):
            for _ in range(n_steps):
                self.st, m = self._step(self.st, mf)
                mets.append(m)
        mets = jax.device_get(mets)
        out = {k: sum(int(m[k]) for m in mets)
               for k in ("local_msgs", "remote_msgs", "migrations",
                         "heu_evals")}
        out["lcr"] = [float(m["lcr"]) for m in mets]
        for k in ("lp_flows", "mig_flows"):
            out[k] = np.sum([m[k] for m in mets], 0, dtype=np.int64).tolist()
        return out

    # -- churn and queries -----------------------------------------------

    def _clear(self, ids):
        st = self.st
        st["pending_dst"] = st["pending_dst"].at[ids].set(-1)
        st["pending_eta"] = st["pending_eta"].at[ids].set(-1)
        st["ring"] = st["ring"].at[:, ids, :].set(0)
        st["last_mig"] = st["last_mig"].at[ids].set(-10**6)

    def depart(self, ids):
        ids = jnp.asarray(np.asarray(ids, np.int32))
        with jax.default_device(self.device):
            self.st["lp"] = self.st["lp"].at[ids].set(-1)
            self._clear(ids)

    def arrive(self, ids, pos, lps):
        """Place arrivals `pos` on LPs `lps` in slots `ids`: at rest, with
        their own position as waypoint and no history."""
        ids = jnp.asarray(np.asarray(ids, np.int32))
        p = jnp.asarray(np.asarray(pos, np.float32)).astype(self.dtype)
        with jax.default_device(self.device):
            self.st["pos"] = self.st["pos"].at[ids].set(p)
            self.st["waypoint"] = self.st["waypoint"].at[ids].set(p)
            self.st["lp"] = self.st["lp"].at[ids].set(
                jnp.asarray(np.asarray(lps, np.int32)))
            self._clear(ids)

    def stripe_lp(self, pos):
        """The LP of an arrival whose LP is not given: the x-stripe of
        the torus its position lies in."""
        pos = np.asarray(pos, np.float32)
        return np.clip((pos[:, 0] / self.area * self.L).astype(np.int32), 0,
                       self.L - 1)

    def neighbors(self, ids) -> dict:
        """{id: sorted live ids within range of it, itself excluded}."""
        pos = np.asarray(self.st["pos"]).astype(np.float32)
        live = np.asarray(self.st["lp"]) >= 0
        out = {}
        for i in ids:
            d = np.abs(pos - pos[i])
            d = np.minimum(d, np.float32(self.area) - d)
            d2 = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
            hit = live & (d2 <= np.float32(self.rng * self.rng))
            hit[i] = False
            out[int(i)] = np.flatnonzero(hit).tolist()
        return out

    def lcr_now(self) -> float:
        """LCR if every live SE sent now."""
        with jax.default_device(self.device):
            valid = self.st["lp"] >= 0
            counts = self._counts_jit(self.st["pos"], self.st["lp"], valid,
                                      valid)
            own = np.clip(np.asarray(self.st["lp"]), 0, self.L - 1)
            c = np.asarray(counts, np.int64)
            local = int(c[np.arange(self.n), own].sum())
            total = int(c.sum())
        return float(np.float32(local) / np.float32(max(total, 1)))

