"""One run of one cell: set-up, the measured window, the reference check
and the result line. `chipbench/run.py` is the entry point."""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import sys
import time

from chipbench import correct, device, traffic

#: recorded for every program compiled or read from the persistent cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: most warm rounds, each run while the one before still compiled
MAX_WARM = 5
#: exit code of a traced run whose trace gave a listed metric nothing
TRACE_BLIND = 4


class Run:
    """What the metric readers see of one run. Times on the host's
    monotonic clock are in seconds; `spans` are the window's calls as
    (name, start, end); `counters` the window's step counters."""

    def __init__(self, **kw):
        self.trace = None
        self.busy_s = self.window_s = None
        self.__dict__.update(kw)


def _load_reader(root: str, kind: str, name: str):
    path = os.path.join(root, "chipbench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics_for(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def _configure_jax():
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # cache every program, however quick to compile, and never evict: the
    # directory is the checkout's own
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    count = [0]

    def on_duration(event, duration, **_):
        if event == _COMPILE_EVENT:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return count


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, sut_factory=None, skip_device_check=False) -> int:
    """Run one cell; print the result line. Returns the exit code.
    `sut_factory(cfg, world, engine_seed)` and `skip_device_check` let the
    benchmark's own tests drive a run without a chip."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        print(f"unknown workload {workload!r}", file=sys.stderr)
        return 2
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    mix = traffic.load(root, cell["traffic"])
    world = mix.get("world", {})

    compiles = _configure_jax()
    import jax
    marks = [("jax import", time.perf_counter())]
    if skip_device_check:
        devs = jax.devices()[:cell["chips"]]
    else:
        try:
            devs = device.require_tpu(cell["chips"])
        except device.NoChip as e:
            print(f"no result: {e}", file=sys.stderr)
            return device.NO_CHIP
    peaks = device.peaks(devs[0].device_kind) if not skip_device_check \
        else None
    marks.append(("devices", time.perf_counter()))

    from chipbench import trace as tr
    hlo = tr.HloCapture().install() if trace else None
    engine_seed = seed % 2**32
    if sut_factory is None:
        from chipbench.sut import ProgramSUT
        sut_factory = ProgramSUT
    sut = sut_factory(cfg, world, engine_seed)
    marks.append(("engine init", time.perf_counter()))
    abm = cfg["engine"]["abm"]
    n_live = int(world.get("n_active", 0)) or int(abm["n_se"])
    client = traffic.Client(
        sut, mix, seed, n_live, float(abm["area"]),
        annotate=jax.profiler.TraceAnnotation if trace else None)
    # warm up: rounds until one compiles (or reads from the cache) no
    # program: a sharded state that comes out of its first window is laid
    # out differently from the one that came from init, and the second
    # window specializes again
    for i in range(MAX_WARM):
        before = compiles[0]
        client.round()
        marks.append((f"warm round {i + 1} ({compiles[0] - before} "
                      f"programs)", time.perf_counter()))
        if compiles[0] == before:
            break
    n_warm_log, n_warm_spans, warm_steps = (len(client.log),
                                            len(client.spans), client.steps)
    live_at_start = len(client.live)
    compiles_before = compiles[0]

    with contextlib.ExitStack() as stack:
        prof = stack.enter_context(tr.profiling()) if trace else None
        t0 = time.perf_counter()
        while True:
            client.round()
            t_end = time.perf_counter()
            if t_end - t0 >= seconds:
                break
    compiles_in_window = compiles[0] - compiles_before
    setup_s = t0 - t_start
    prev, parts = t_start, []
    for name, t in marks:
        parts.append(f"{name} {t - prev:.3f} s")
        prev = t
    peak = device.memory_peak_bytes(devs) if not skip_device_check else 0

    run_ = Run(cfg=cfg, cell=cell, mix=mix, peaks=peaks, setup_s=setup_s,
               t0=t0, t_end=t_end, steps=client.steps - warm_steps,
               n_live=live_at_start, spans=client.spans[n_warm_spans:],
               counters=[e[2] for e in client.log[n_warm_log:]
                         if e[0] == "step"])
    attempted = len(run_.spans)

    final = sut.export()
    sut.close()
    del sut, client.sut
    gc.collect()

    values = correct.check(cfg, world, engine_seed, client.log, final,
                           device=devs[0])
    ok = correct.verdict(values)

    if trace:
        hlo.uninstall()
        t_red = time.perf_counter()
        scopes = hlo.scopes()
        trc = tr.Trace.from_file(prof["path"], scopes)
        tr.cleanup(prof)
        if trc.devices:  # none on the CPU, which has no device planes
            if not any(tr.phase_of(op) for mod in scopes.values()
                       for op in mod.values()):
                print("no result: the compiled programs hold no "
                      "step.<phase> scope", file=sys.stderr)
                return TRACE_BLIND
            run_.trace = trc
            w0, w1 = trc.window()
            run_.window_s = (w1 - w0) / 1e9
            run_.busy_s = sum(d.busy_ns(w0, w1)
                              for d in trc.devices.values()
                              ) / 1e9 / len(trc.devices)
        print(f"trace reduced in {time.perf_counter() - t_red:.3f} s: "
              f"{sum(len(d.ops) for d in trc.devices.values())} ops on "
              f"{len(trc.devices)} devices, {len(trc.spans)} host spans",
              file=sys.stderr)

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in _metrics_for(bench, section, workload):
        v = _load_reader(root, "e2e" if section == "end_to_end"
                         else "metrics", m["name"])(run_)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    if run_.trace is not None:
        blind = [m["name"] for m in _metrics_for(bench, section, workload)
                 if m["name"] not in metrics]
        if blind:
            print(f"no result: the trace gave nothing to {blind}",
                  file=sys.stderr)
            return TRACE_BLIND

    dev = device.describe(devs)
    dev["memory_peak_bytes"] = peak
    out = {"correct": ok, "attempted": attempted,
           "failed": values["answer_diffs"] + values["counter_diffs"],
           "metrics": metrics, "device": dev}
    if run_.trace is not None:
        dev["busy_s"] = run_.busy_s
        dev["window_s"] = run_.window_s
        out["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in trc.top_ops(0)],
            "idle_gaps": [[n, g / 1e9] for n, g in sorted(
                trc.named_gaps(0), key=lambda x: -x[1])[:10]]}
    out["checks"] = {k: {"value": values[k], "limit": lim}
                     for k, lim in correct.LIMITS.items()}

    print("set-up: " + ", ".join(parts), file=sys.stderr)
    print(f"compiles in the window: {compiles_in_window}", file=sys.stderr)
    print(f"window: {run_.steps} steps, {attempted} calls, "
          f"{t_end - t0:.6f} s; set-up {setup_s:.6f} s", file=sys.stderr)
    for line in correct.report_lines(values):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
