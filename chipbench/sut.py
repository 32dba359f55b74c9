"""The system under test behind one interface, and the reference behind
the same interface (the control that the comparison must reject).

Both expose: step(n) -> window counters, depart(ids), arrive(pos) ->
ids, query_neighbors(ids) -> {id: [ids]}, query_lcr() -> float,
export() -> the state in id order on the host, and close()."""
from __future__ import annotations

import numpy as np

#: the state fields the comparison reads, by their id-order names
STATE_KEYS = ("pos", "waypoint", "lp", "pending_dst", "pending_eta",
              "ring", "last_mig")


def engine_config(cfg: dict, world: dict):
    """The program's EngineConfig from a configuration file's `engine`
    block and a traffic mix's `world` block."""
    from repro.core import EngineConfig
    from repro.core.abm import ABMConfig
    from repro.core.heuristics import HeuristicConfig
    e = dict(cfg["engine"])
    abm = ABMConfig(**e.pop("abm"))
    heu = HeuristicConfig(**e.pop("heuristic"))
    return EngineConfig(abm=abm, heuristic=heu, **e, **world)


class ProgramSUT:
    """`repro.core.Engine`, driven through its public methods."""

    def __init__(self, cfg: dict, world: dict, seed: int):
        from repro.core import Engine
        self.ecfg = engine_config(cfg, world)
        self.sharded = self.ecfg.sharding == "lp_device"
        self.eng = Engine(self.ecfg).init(seed=seed)

    def step(self, n: int) -> dict:
        return self.eng.step(n)

    def depart(self, ids) -> None:
        self.eng.depart(ids)

    def arrive(self, pos) -> list:
        return self.eng.arrive({"pos": pos})

    def query_neighbors(self, ids) -> dict:
        return self.eng.query_neighbors(ids)

    def query_lcr(self) -> float:
        return self.eng.query_lcr()

    def export(self) -> dict:
        """Id-order host copy of the state. The sharded layer keeps rows
        in device slots with their global id in `gid`; rows are put back
        in id order here, by the benchmark and not by the program."""
        import jax
        st = jax.device_get(self.eng.state)
        out = {"t": np.asarray(st["t"]),
               "key": np.asarray(jax.random.key_data(self.eng.state["key"]))}
        if not self.sharded:
            out.update({k: np.asarray(st[k]) for k in STATE_KEYS})
            return out
        n = self.ecfg.abm.n_se
        gid = np.asarray(st["gid"])
        live = gid >= 0
        if np.unique(gid[live]).size != live.sum():
            raise ValueError("sharded state holds one id in two slots")
        for k in STATE_KEYS:
            v = np.asarray(st[k])
            if k == "ring":
                o = np.zeros((v.shape[0], n) + v.shape[2:], v.dtype)
                o[:, gid[live]] = v[:, live]
            else:
                fill = -1 if k in ("lp", "pending_dst", "pending_eta") else 0
                if k == "last_mig":
                    fill = -10**6
                o = np.full((n,) + v.shape[1:], fill, v.dtype)
                o[gid[live]] = v[live]
            out[k] = o
        return out

    def close(self) -> None:
        self.eng = None


class ReferenceSUT:
    """The plain reference in the program's place, in a chosen precision
    of positions: the control of the comparison."""

    def __init__(self, cfg: dict, world: dict, seed: int, dtype):
        from chipbench.reference import World
        m = cfg["engine"]["abm"]
        self.n = int(m["n_se"])
        live = int(world.get("n_active", 0)) or self.n
        self.w = World(cfg["engine"], seed, live, dtype=dtype)
        self.free = list(range(self.n - 1, live - 1, -1))

    def step(self, n: int) -> dict:
        c = self.w.step(n)
        lcr = np.asarray(c.pop("lcr"), np.float32)
        c = {k: (float(v) if not isinstance(v, list) else v)
             for k, v in c.items()}
        c["mean_lcr"] = float(lcr.mean())
        c["grid_overflow"] = 0.0
        return c

    def depart(self, ids) -> None:
        self.w.depart(ids)
        self.free.extend(reversed([int(i) for i in ids]))

    def arrive(self, pos) -> list:
        ids = [self.free.pop() for _ in range(len(pos))]
        self.w.arrive(ids, pos, self.w.stripe_lp(pos))
        return ids

    def query_neighbors(self, ids) -> dict:
        return self.w.neighbors(ids)

    def query_lcr(self) -> float:
        return self.w.lcr_now()

    def export(self) -> dict:
        st = self.w.numpy_state()
        st["pos"] = st["pos"].astype(np.float32)
        st["waypoint"] = st["waypoint"].astype(np.float32)
        return st

    def close(self) -> None:
        self.w = None

