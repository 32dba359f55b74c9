"""The one generator of traffic: it reads a mix, a data file
`chipbench/traffic/<mix>.json`, and drives a system under test with it.

A mix holds:

  world        EngineConfig fields the mix needs (an open world and its
               live population), merged into the configuration
  round        the operations of one round, in order:
                 {"op": "step", "n": K}             advance K steps
                 {"op": "depart", "count": B}       B live ids leave
                 {"op": "arrive", "count": B}       B SEs arrive at
                                                    uniform positions
                 {"op": "query_neighbors", "count": Q}  neighbours of Q
                                                    live ids, one call
                 {"op": "query_lcr"}                the instantaneous LCR

One client runs rounds back to back (a closed loop). Every choice is
drawn from the run's seed by one generator, so a seed gives the same
sizes and the same order of operations every time; which ids depart or
are queried is drawn from the live set the client holds, which follows
the ids the system returned.
"""
from __future__ import annotations

import contextlib
import json
import os
import time

import numpy as np

OPS = ("step", "depart", "arrive", "query_neighbors", "query_lcr")


def load(root: str, name: str) -> dict:
    path = os.path.join(root, "chipbench", "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    for op in mix["round"]:
        if op["op"] not in OPS:
            raise ValueError(f"{path}: unknown op {op['op']!r}")
    return mix


class Client:
    """Drives one system under test; records every operation in `log`
    (for the reference) and every call as a (name, start, end) span on
    the host's monotonic clock."""

    def __init__(self, sut, mix: dict, seed: int, n_live: int, area: float,
                 annotate=None):
        self.sut = sut
        self.mix = mix
        self.rng = np.random.default_rng(seed)
        self.live = set(range(n_live))
        self.area = np.float32(area)
        self.log = []
        self.spans = []
        self.steps = 0
        self.annotate = annotate or (lambda name: contextlib.nullcontext())

    def _call(self, name, fn, *args):
        with self.annotate(f"bench.{name}"):
            t0 = time.perf_counter()
            out = fn(*args)
            t1 = time.perf_counter()
        self.spans.append((name, t0, t1))
        return out

    def _pick(self, count: int) -> list:
        pool = sorted(self.live)
        return [pool[i] for i in self.rng.choice(len(pool), count,
                                                 replace=False)]

    def round(self) -> None:
        for op in self.mix["round"]:
            kind = op["op"]
            if kind == "step":
                c = self._call("step", self.sut.step, op["n"])
                self.steps += op["n"]
                self.log.append(("step", op["n"], c))
            elif kind == "depart":
                ids = self._pick(op["count"])
                self._call("depart", self.sut.depart, ids)
                self.live.difference_update(ids)
                self.log.append(("depart", ids))
            elif kind == "arrive":
                pos = self.rng.uniform(0.0, float(self.area),
                                       (op["count"], 2)).astype(np.float32)
                pos = np.minimum(pos, np.nextafter(self.area, 0))
                ids = [int(i) for i in
                       self._call("arrive", self.sut.arrive, pos)]
                self.live.update(ids)
                self.log.append(("arrive", pos, ids))
            elif kind == "query_neighbors":
                ids = self._pick(op["count"])
                ans = self._call("query_neighbors",
                                 self.sut.query_neighbors, ids)
                self.log.append(("query_neighbors", ids, ans))
            else:
                ans = self._call("query_lcr", self.sut.query_lcr)
                self.log.append(("query_lcr", ans))
