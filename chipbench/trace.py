"""From a profiler trace to the numbers the per-layer metrics read.

How the v5e trace looks (read by hand from a one-chip trace, JAX 0.9):
each TPU is a plane `/device:TPU:<k>` with the lines `XLA Modules` (one
event per executed program, named `jit_<fn>(<fingerprint>)`), `XLA Ops`
(one event per HLO instruction, named by its HLO text `%fusion.422 =
...`; a `while` event spans the ops of its body, which nest inside it)
and `Async XLA Ops` (copy-start/done and other asynchronous pairs,
whose spans overlap the work). Events carry no scope name. The scope of
an instruction (`jax.named_scope`, e.g. `step.proximity`) is in the
`op_name` metadata of the compiled HLO, which `HloCapture` records as
programs are compiled or read from the cache. The harness's own
`jax.profiler.TraceAnnotation` spans (`bench.<call>`) are events on a
host plane, on the same clock as the device events.

Reduction, per device:
  busy      the union of the intervals of leaf ops on `XLA Ops` (ops
            with no op nested inside them)
  self time an op's duration less the ops nested inside it
  idle gaps the complement of busy within the traced window, each named
            by the host span that overlaps it most
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import shutil
import tempfile

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%?([\w.\-]+)")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMP_HEAD = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_SCOPE = re.compile(r"step\.([A-Za-z_]+)")
_COLLECTIVE = re.compile(
    r"\s(all-gather|all-reduce|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(-start|-done)?\(")


class HloCapture:
    """Records the optimized HLO of every program compiled (or read from
    the persistent cache) while installed, to name trace ops by scope."""

    def __init__(self):
        self._exes = []
        self._orig = None

    def install(self) -> "HloCapture":
        from jax._src import compiler
        self._orig = compiler.compile_or_get_cached

        def capture(*args, **kwargs):
            exe = self._orig(*args, **kwargs)
            self._exes.append(exe)
            return exe

        compiler.compile_or_get_cached = capture
        return self

    def uninstall(self) -> None:
        if self._orig is not None:
            from jax._src import compiler
            compiler.compile_or_get_cached = self._orig
            self._orig = None

    def scopes(self) -> dict:
        """{module name: {instruction name: op_name}}."""
        out = collections.defaultdict(dict)
        for exe in self._exes:
            for mod in exe.hlo_modules():
                out[mod.name].update(parse_hlo_scopes(mod.to_string()))
        return dict(out)


def parse_hlo_scopes(text: str) -> dict:
    """{instruction: op_name} of one HLO module's text. A fusion with no
    metadata of its own takes the most common scope of its body."""
    comps = collections.defaultdict(list)
    own, calls = {}, {}
    comp = None
    for line in text.splitlines():
        head = _COMP_HEAD.match(line)
        if head and "=" not in line.split("{")[0]:
            comp = head.group(1)
            continue
        m = _HLO_LINE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
            if comp is not None:
                comps[comp].append(op.group(1))
        c = _CALLS.search(rest)
        if c:
            calls[name] = c.group(1)
    for name, comp in calls.items():
        if name not in own and comps.get(comp):
            own[name] = collections.Counter(comps[comp]).most_common(1)[0][0]
    return own


def phase_of(op_name: str):
    m = _SCOPE.search(op_name or "")
    return m.group(1) if m else None


class Device:
    """One device's modules and leaf ops, times in ns on the trace clock."""

    def __init__(self):
        self.modules = []  # (start, end, name)
        self.ops = []  # (start, end, self_ns, text, is_leaf)
        self.async_ops = []  # (start, end, text)
        self.busy = []  # merged (start, end) of the leaf ops
        self._mstarts = []

    def module_at(self, t):
        i = bisect.bisect_right(self._mstarts, t) - 1
        if i >= 0 and self.modules[i][0] <= t <= self.modules[i][1]:
            return self.modules[i][2]
        return None

    def busy_ns(self, t0, t1) -> float:
        return sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in self.busy
                   if e > t0 and s < t1)

    def gaps(self, t0, t1) -> list:
        out, cur = [], t0
        for s, e in self.busy:
            if e <= t0:
                continue
            if s >= t1:
                break
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < t1:
            out.append((cur, t1))
        return out


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _nest(events):
    """(start, end, name) events of one line -> ops as [start, end,
    self_ns, name, is_leaf], an op nested in another counting against
    the other's self time."""
    events = sorted(events, key=lambda x: (x[0], -x[1]))
    stack, out = [], []
    for s, e, name in events:
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, e - s, name, True]
        if stack and e <= stack[-1][1]:
            parent = stack[-1][2]
            parent[2] -= e - s
            parent[4] = False
        stack.append((s, e, rec))
        out.append(rec)
    return out


class Trace:
    """A reduced trace: devices by id, and the host spans `bench.*`."""

    def __init__(self, devices: dict, spans: list, scopes: dict):
        self.devices = devices
        self.spans = sorted(spans)  # (start, end, name)
        self.scopes = scopes

    @classmethod
    def from_file(cls, path: str, scopes: dict) -> "Trace":
        with open(path, "rb") as f:
            return cls.from_bytes(f.read(), scopes)

    @classmethod
    def from_bytes(cls, data: bytes, scopes: dict) -> "Trace":
        """From a serialized XSpace (the content of an `.xplane.pb`)."""
        from jax.profiler import ProfileData
        pd = ProfileData.from_serialized_xspace(data)
        devices, spans = {}, []
        for plane in pd.planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                dev = devices.setdefault(int(m.group(1)), Device())
                for line in plane.lines:
                    evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                    if line.name == "XLA Modules":
                        dev.modules.extend(evs)
                    elif line.name == "XLA Ops":
                        for s, e, sf, name, leaf in _nest(evs):
                            if leaf or sf > 0:
                                dev.ops.append((s, e, sf, name, leaf))
                    elif line.name == "Async XLA Ops":
                        dev.async_ops.extend(evs)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            spans.append((e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          e.name[len("bench."):]))
        for d in devices.values():
            d.modules.sort()
            d._mstarts = [x[0] for x in d.modules]
            d.ops.sort()
            d.busy = _union([(s, e) for s, e, sf, n, leaf in d.ops if leaf])
        return cls(devices, spans, scopes)

    # -- the window ------------------------------------------------------

    def window(self):
        """(start, end) of the traced window: the first host span's start
        to the last one's end."""
        if not self.spans:
            return None
        return self.spans[0][0], max(e for _, e, _ in self.spans)

    # -- readings --------------------------------------------------------

    def op_name(self, dev: Device, op) -> str:
        s, e, sf, text, leaf = op
        mod = dev.module_at(s)
        mname = _MODULE.match(mod).group(1) if mod else ""
        instr = _INSTR.match(text).group(1)
        return self.scopes.get(mname, {}).get(instr, "")

    def self_by_phase(self, dev_id: int) -> dict:
        """{phase or None: self ns} over the window, on one device."""
        dev = self.devices[dev_id]
        t0, t1 = self.window()
        out = collections.Counter()
        for op in dev.ops:
            if op[0] >= t0 and op[1] <= t1:
                out[phase_of(self.op_name(dev, op))] += op[2]
        return dict(out)

    def collective_ns(self, dev_id: int) -> float:
        """Time during which a collective op ran on one device (the
        union of the collective ops' intervals, sync and async)."""
        dev = self.devices[dev_id]
        t0, t1 = self.window()
        iv = [(s, e) for s, e, sf, text, leaf in dev.ops
              if _COLLECTIVE.search(text)]
        iv += [(s, e) for s, e, text in dev.async_ops
               if _COLLECTIVE.search(text)]
        return sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in _union(iv))

    def named_gaps(self, dev_id: int) -> list:
        """[(name of the host span it fell in, ns)] for every idle gap."""
        dev = self.devices[dev_id]
        t0, t1 = self.window()
        out = []
        starts = [s for s, _, _ in self.spans]
        for g0, g1 in dev.gaps(t0, t1):
            best, name = 0.0, "between calls"
            i = max(0, bisect.bisect_right(starts, g0) - 1)
            for s, e, n in self.spans[i:]:
                if s >= g1:
                    break
                ov = min(e, g1) - max(s, g0)
                if ov > best:
                    best, name = ov, n
            out.append((name, g1 - g0))
        return out

    def top_ops(self, dev_id: int, k: int = 10) -> list:
        """The k (scope / instruction) groups of most self time."""
        dev = self.devices[dev_id]
        t0, t1 = self.window()
        tot = collections.Counter()
        for op in dev.ops:
            if op[0] >= t0 and op[1] <= t1:
                instr = _INSTR.match(op[3]).group(1)
                kind = re.sub(r"[.\d]+$", "", instr)
                scope = phase_of(self.op_name(dev, op))
                tot[f"step.{scope}/{kind}" if scope else kind] += op[2]
        return tot.most_common(k)


@contextlib.contextmanager
def profiling():
    """Run the body under the JAX profiler (device and host-span tracer
    only, no Python tracer); yields a dict whose "path" is the
    `.xplane.pb` file once the body ends. The trace directory lives
    under TMPDIR and is removed by `cleanup`."""
    import jax
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out = {"dir": d, "path": None}
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out["path"] = found[0] if found else None


def cleanup(prof: dict) -> None:
    shutil.rmtree(prof["dir"], ignore_errors=True)
