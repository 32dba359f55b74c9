"""Median of the window's `arrive` and `depart` calls, in ms, timed by
the client."""
from chipbench.stats import percentile


def read(run):
    ms = [1e3 * (t1 - t0) for name, t0, t1 in run.spans
          if name in ("arrive", "depart")]
    return percentile(ms, 50) if ms else None
