"""Runtime telemetry for the GAIA engine (DESIGN.md §Observability).

Two pillars, off by default (`ObsConfig.enabled = False` — a
telemetry-off config shares compiled executables with a config that
never heard of telemetry, and telemetry-on never perturbs PRNG streams
or results):

* **metrics ledger** (`ledger`): a fixed-shape on-device ring buffer of
  per-step counters, drained asynchronously to the host every
  `drain_every` steps via one unordered `jax.debug.callback` — the
  memoized single-scan architecture is never broken per step;
* **event log** (`events`): typed, step-stamped records (migration
  bursts, repartitions, overflow alarms, churn batches, tuner moves)
  through pluggable sinks (memory / JSONL / stdout).

`core.service.Engine.metrics()/events()/prometheus()` is the serving
surface. Apart from both, always on: **program spans** (`trace`), host
spans at each layer boundary of `Engine`'s calls, written into the JAX
profiler's trace beside the device ops.
"""
from repro.obs.config import ObsConfig
from repro.obs.events import (EVENT_KINDS, Event, EventLog, JsonlSink,
                              MemorySink, StdoutSink)
from repro.obs.ledger import MetricsLedger, Telemetry, ledger_keys
from repro.obs.prom import prometheus_text
from repro.obs import runtime
from repro.obs.trace import Spans

__all__ = [
    "ObsConfig", "EVENT_KINDS", "Event", "EventLog", "JsonlSink",
    "MemorySink", "StdoutSink", "MetricsLedger", "Telemetry",
    "ledger_keys", "prometheus_text", "runtime", "Spans",
]
