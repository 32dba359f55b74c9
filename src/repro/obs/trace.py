"""Program spans: host intervals at the engine's layer boundaries,
written into the JAX profiler's trace on the same clock as the device
ops (`jax.profiler.TraceAnnotation`).

Every span is named `gaia.<call>[.<stage>...]`, its path of open spans
on the calling thread, so parenthood is the nesting. Each carries the
`call=<n>` id of the call it belongs to, and counters ride as further
arguments (the profiler keeps them as event stats). With the profiler
off a span costs a few microseconds, so spans are always on; none sits
inside jitted code, whose Python body runs only while tracing.
Open a trace of a run (`jax.profiler.trace(dir)`) in Perfetto or
TensorBoard to see them beside the device ops.
"""
from __future__ import annotations

import contextlib
import itertools

from jax.profiler import TraceAnnotation

PREFIX = "gaia."


class Spans:
    """The spans of one caller's calls (one per `core.service.Engine`):
    `call(name)` opens a call's span under a fresh id, `child(name)` a
    stage of the innermost open span. Both yield the annotation, whose
    `set_metadata(**args)` adds arguments known only inside the span."""

    def __init__(self):
        self._ids = itertools.count(1)
        self._path = []
        self._call = 0

    @contextlib.contextmanager
    def call(self, name: str, **args):
        outer, self._call = self._call, next(self._ids)
        try:
            with self.child(name, **args) as span:
                yield span
        finally:
            self._call = outer

    @contextlib.contextmanager
    def child(self, name: str, **args):
        self._path.append(name)
        try:
            with TraceAnnotation(PREFIX + ".".join(self._path),
                                 call=self._call, **args) as span:
                yield span
        finally:
            self._path.pop()
